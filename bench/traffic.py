"""The one generator of every traffic mix.

A mix is a data file ``bench/traffic/<name>.json``; this module turns it and
a seed into tables, query texts and (for an open loop) a schedule.  Sizes
and arrivals are a fixed set drawn from quantiles of the distribution the
file states; the seed only orders them and fills in the words, so every
seed gives the same amount of work.
"""
from __future__ import annotations

import json
import string
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str, bench_dir: Path = HERE) -> dict:
    """The mix ``bench/traffic/<name>.json``; a ``table`` given as a name
    is that mix's table."""
    path = bench_dir / "traffic" / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    if isinstance(mix["table"], str):
        mix["table"] = load(mix["table"], bench_dir)["table"]
    return mix


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (k + 0.5) / n of the stated
    distribution, clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _slots(template: str) -> List[str]:
    return sorted({f for _, f, _, _ in string.Formatter().parse(template)
                   if f})


def text_of(rng: np.random.Generator, spec: dict, length: int) -> str:
    """ASCII text of exactly ``length`` bytes: an optional ``lead`` (its
    ``{num}`` an eight-digit number, as a report's case number), then
    sentences from the file's templates with each ``{slot}`` filled from
    its word list, entered at a random word of the first sentence."""
    lead = spec.get("lead", "").format(num=rng.integers(10 ** 7, 10 ** 8))
    length -= len(lead)
    words: Dict[str, Sequence[str]] = spec["words"]
    temps = spec["templates"]
    parts: List[str] = []
    size = 0
    while size < length + 64:
        t = temps[rng.integers(len(temps))]
        slots = _slots(t)
        picks = rng.integers(0, 1 << 30, len(slots))
        s = t.format(**{k: words[k][p % len(words[k])]
                        for k, p in zip(slots, picks)})
        parts.append(s)
        size += len(s) + 1
    text = " ".join(parts)
    starts = [0] + [i + 1 for i, c in enumerate(text[:64]) if c == " "]
    text = text[starts[rng.integers(len(starts))]:]
    return lead + text[:length].rstrip().ljust(length, ".")


def make_rows(rng: np.random.Generator, table: dict, lengths: Sequence[int],
              first_id: int, seen: set) -> List[dict]:
    """One row per length; texts are unique across ``seen``."""
    rows = []
    for i, n in enumerate(lengths):
        row = {table["key"]: first_id + i}
        for col, spec in table.get("labels", {}).items():
            row[col] = spec[rng.integers(len(spec))]
        for _ in range(100):
            text = text_of(rng, table["text"], int(n))
            if text not in seen:
                break
        else:
            raise ValueError(f"no unique text of {n} bytes in 100 draws")
        seen.add(text)
        row[table["text_column"]] = text
        rows.append(row)
    return rows


def schema_of(table: dict) -> Dict[str, str]:
    sch = {table["key"]: "INTEGER"}
    sch.update({c: "VARCHAR" for c in table.get("labels", {})})
    sch[table["text_column"]] = "VARCHAR"
    return sch


def closed_loop_rows(mix: dict, seed: int, n_queries: int,
                     seen: set) -> List[dict]:
    """Rows of ``n_queries`` consecutive slices of ``rows_per_query`` rows;
    every slice holds the same multiset of lengths, in its own order."""
    rng = np.random.default_rng([seed, 1])
    per = mix["rows_per_query"]
    base = quantile_lengths(mix["table"]["length_tokens"], per)
    rows: List[dict] = []
    for k in range(n_queries):
        rows += make_rows(rng, mix["table"], rng.permutation(base), k * per,
                          seen)
    return rows


def warmup_rows(mix: dict, seed: int, first_id: int,
                seen: set) -> List[dict]:
    """Rows that warm every shape: the shortest and the longest lengths
    of the mix and evenly spaced ones between, ``warmup_rows`` in all."""
    rng = np.random.default_rng([seed, 2])
    n = mix["warmup_rows"]
    # as many quantiles as the mix's largest set of lengths has
    most = max(n, mix.get("rows_per_query", n),
               mix.get("open", {}).get("table_rows", n))
    base = np.sort(quantile_lengths(mix["table"]["length_tokens"], most))
    pick = base[np.linspace(0, len(base) - 1, n).round().astype(int)]
    return make_rows(rng, mix["table"], pick, first_id, seen)


def open_loop_schedule(mix: dict, seed: int, seconds: float,
                       seen: set) -> dict:
    """Queries due in a window of ``seconds``: a fixed set of Poisson gaps
    (exponential mid-quantiles at ``rate_qps``), each query with a row of a
    fixed length.  The order of gaps and lengths is the mix's own trace
    (``trace_seed``): in an open loop the order is part of the work, and
    queueing makes a tail swing with it.  The seed picks the rows' ids,
    their words and the tenants (in their Zipf shares)."""
    op = mix["open"]
    rate = float(op["rate_qps"])
    n = max(1, int(rate * seconds))          # all n fall inside the window
    trace = np.random.default_rng([op["trace_seed"], 3])
    q = (np.arange(n) + 0.5) / n
    due = np.cumsum(trace.permutation(-np.log1p(-q) / rate))
    spec = mix["table"]["length_tokens"]
    due_lengths = trace.permutation(quantile_lengths(spec, n))
    rng = np.random.default_rng([seed, 3])
    shares = 1.0 / np.arange(1, op["tenants"] + 1) ** op["zipf_s"]
    shares = shares / shares.sum() * n
    counts = np.floor(shares).astype(int)
    for i in np.argsort(counts - shares)[:n - counts.sum()]:
        counts[i] += 1
    tenants = rng.permutation(np.repeat(np.arange(op["tenants"]), counts))
    total = op["table_rows"]
    if n > total:
        raise ValueError(f"{n} lookups need more than {total} table rows")
    ids = rng.choice(total, size=n, replace=False)
    lengths = np.zeros(total, int)
    rest = np.setdiff1d(np.arange(total), ids)
    lengths[rest] = rng.permutation(quantile_lengths(spec, total - n))
    lengths[ids] = due_lengths
    rows = make_rows(rng, mix["table"], lengths, 0, seen)
    keep = due < seconds
    return {"due": due[keep], "ids": ids[keep], "tenants": tenants[keep],
            "rows": rows, "rate_qps": rate}
