"""Helpers the metric readers under ``bench/metrics`` share.  A reader
returns None where its cell gives it nothing to read."""
from __future__ import annotations

import math
from typing import List, Optional


def window_span_s(ctx) -> float:
    """From the window's start to the end of the last query it counts."""
    w = ctx["window"]
    return w["end"] - w["start"]


def total(ctx, key: str) -> int:
    return sum(q["stats"][key] for q in ctx["queries"] if q["stats"])


def calls_per_dispatch(ctx) -> Optional[float]:
    batches = total(ctx, "dispatch_batches")
    return total(ctx, "llm_calls") / batches if batches else None


def idle_share(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def nearest_rank(values: List[float], pct: float) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(pct / 100.0 * len(s)) - 1)]


def decode_work(ctx):
    """(operations, row-step contexts) of every decode step the window's
    served requests took."""
    dims, flops, ctxs = ctx["dims"], 0, []
    for r in ctx["requests"]:
        p, n = len(r["prompt"]), len(r["text"])     # served = text + EOS
        flops += dims.request_flops(p, n + 1)[1]
        ctxs.extend(p + j + 1 for j in range(n))
    return flops, ctxs
