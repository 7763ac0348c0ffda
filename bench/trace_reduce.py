"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-program
device time and the longest idle gaps, each gap named by the benchmark's own
host span that was open across it.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with the lines
``XLA Ops`` (one event per operation) and ``XLA Modules`` (one event per
executed program, named after its jitted function).  Host threads live on
``/host:CPU``; the benchmark's ``jax.profiler.TraceAnnotation`` spans there
all start with ``SPAN_PREFIX``, and the one named ``WINDOW_SPAN`` bounds the
window that is reduced.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """``jit_decode_step(12)`` → ``decode_step``."""
    name = re.sub(r"\(\d+\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def read(path: str, *, device_prefix: str = "/device:TPU:",
         ops_line: str = "XLA Ops", modules_line: str = "XLA Modules"
         ) -> dict:
    """Collect the raw intervals of a trace: per device plane the op
    intervals and the (program, start, end) module events, and every host
    span of the benchmark.  ``ops_line`` and ``modules_line`` match line
    names by prefix."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name.startswith(ops_line):
                    dev["ops"].extend((s, e) for _, s, e in _events(line))
                elif modules_line and line.name.startswith(modules_line):
                    dev["modules"].extend(
                        (program_name(n), s, e) for n, s, e in _events(line))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def _open_span(spans, t: int) -> str:
    """Innermost (latest-starting) benchmark span open at time ``t``; a
    span of waiting (``bench.await_*``, open on the load generator's
    thread) names a gap only when no span of work is open."""
    best: Optional[Tuple[bool, int, str]] = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e:
            key = (not name.startswith(SPAN_PREFIX + "await_"), s, name)
            if best is None or key > best:
                best = key
    return best[2][len(SPAN_PREFIX):] if best else "no span"


def reduce(raw: dict) -> dict:
    """Busy and idle seconds inside the window, averaged over the device
    planes; device seconds per program; the ``breakdown`` lists."""
    win = [(s, e) for n, s, e in raw["spans"] if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    if not raw["devices"]:
        raise ValueError("the trace has no device plane")
    busy_ns, programs, runs, all_gaps = [], {}, {}, []
    for name, dev in sorted(raw["devices"].items()):
        busy = merge(clip(dev["ops"], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for prog, s, e in dev["modules"]:
            (s, e), = clip([(s, e)], lo, hi) or [(0, 0)]
            if e > s:
                programs[prog] = programs.get(prog, 0) + (e - s)
                runs[prog] = runs.get(prog, 0) + 1
        all_gaps.extend(gaps(busy, lo, hi))
    n = len(raw["devices"])
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "program_s": {p: ns / n / 1e9 for p, ns in programs.items()},
        "program_n": {p: c / n for p, c in runs.items()},
        "breakdown": {
            "device_ops": [[p, ns / n / 1e9] for p, ns in sorted(
                programs.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_open_span(raw["spans"], (s + e) // 2),
                           (e - s) / 1e9] for s, e in longest],
        },
    }
