"""The program's own spans in a profiler trace, against the device's busy
time.

The program opens ``jax.profiler.TraceAnnotation`` spans named ``ipdb.*``
(the names are listed in ``src/repro/core/trace.py``) on the same clock as
the device's operations.  ``read`` collects them with the host thread each
ran on; ``reduce`` gives, inside the benchmark's window:

- ``spans``: per span name its count, total seconds, self seconds (less the
  spans nested in it on the same thread), the device-idle seconds inside
  that self time, and of those the part in which a program was executing
  on the device between two of its operations (``op_gap_s``; the rest is
  idle with no program running);
- ``idle_gaps``: the longest idle gaps of the device, each named by the
  innermost program span of work open across it, else by the benchmark's
  span as ``trace_reduce`` names it (a span of waiting, ``await_*``, names a
  gap only where no span of work is open), with its seconds and how many
  seconds into the ``engine.run`` open across it it began (None outside
  one);
- ``dispatch_gaps``: the same, for the gaps inside a ``bench.dispatch``;
- ``plan_ms``: self time of ``sql.parse``, ``sql.bind`` and
  ``sql.optimize`` per query (each query parses once), in milliseconds;
- ``tick_idle_share``: device-idle time inside the union of the
  ``engine.tick`` spans over that union, in %.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs one cell traced, as ``bench/run.py --trace 1`` does, and prints its
result line with the reduction above under ``program``, beside the cell's
end-to-end metrics as the traced run read them (``program.traced``: the
cost of tracing, against an untraced run of the same seed).  Exits
non-zero, printing no result, without a TPU.
"""
from __future__ import annotations

import bisect
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import trace_reduce  # noqa: E402

PREFIX = "ipdb."
AWAIT = "await_"
PLAN_SPANS = ("sql.parse", "sql.bind", "sql.optimize")
TICK_SPAN = "engine.tick"
RUN_SPAN = "engine.run"

# (name without the prefix, host thread, start ns, end ns)
Span = Tuple[str, int, int, int]
Interval = Tuple[int, int]


def read(path: str) -> List[Span]:
    """Every program span of the trace, its metadata suffix (``#...#``)
    cut from its name, with a number for the host thread it ran on."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Span] = []
    thread = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append((e.name.split("#")[0][len(PREFIX):], thread,
                                s, s + int(e.duration_ns)))
    return out


class Busy:
    """A device's merged busy intervals, asked how much of ``[a, b)`` they
    cover in logarithmic time."""

    def __init__(self, merged: Sequence[Interval]):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + e - s)

    def _upto(self, t: int) -> int:
        """Busy time before ``t``."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def within(self, a: int, b: int) -> int:
        return self._upto(b) - self._upto(a) if b > a else 0


def self_pieces(spans: Sequence[Span]) -> List[List[Interval]]:
    """For each span, the parts of its interval that no span nested in it
    on its own thread covers (spans of one thread nest properly)."""
    pieces: List[List[Interval]] = [[] for _ in spans]
    by_thread: Dict[int, List[int]] = {}
    for i, (_, th, _, _) in enumerate(spans):
        by_thread.setdefault(th, []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i][2], -spans[i][3]))
        kids: Dict[int, List[Interval]] = {i: [] for i in idx}
        stack: List[int] = []
        for i in idx:
            s, e = spans[i][2], spans[i][3]
            while stack and spans[stack[-1]][3] <= s:
                stack.pop()
            if stack:
                kids[stack[-1]].append((s, e))
            stack.append(i)
        for i in idx:
            pieces[i] = trace_reduce.gaps(trace_reduce.merge(kids[i]),
                                          spans[i][2], spans[i][3])
    return pieces


def _name_at(spans: Sequence[Span], bench_spans, t: int) -> str:
    """The innermost (latest-starting) span of work open at ``t``: the
    program's first, then the benchmark's; spans of waiting only where no
    span of work is open."""
    best: Optional[tuple] = None
    for name, _, s, e in spans:
        if s <= t < e:
            key = (not name.startswith(AWAIT), 1, s, name)
            best = key if best is None or key > best else best
    for name, s, e in bench_spans:
        if name != trace_reduce.WINDOW_SPAN and s <= t < e:
            short = name[len(trace_reduce.SPAN_PREFIX):]
            key = (not short.startswith(AWAIT), 0, s, short)
            best = key if best is None or key > best else best
    return best[3] if best else "no span"


def reduce(spans: Sequence[Span], raw: dict) -> dict:
    """The program spans of one trace against its device planes, inside the
    benchmark's window; ``raw`` is ``trace_reduce.read``'s."""
    win = [(s, e) for n, s, e in raw["spans"] if n == trace_reduce.WINDOW_SPAN]
    if not win or not raw["devices"]:
        raise ValueError("the trace has no window span or no device plane")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    queries = sum(1 for n, _, s, _ in spans if n == "sql.parse"
                  and lo <= s < hi)
    spans = [(n, th, max(s, lo), min(e, hi)) for n, th, s, e in spans
             if e > lo and s < hi]
    pieces = self_pieces(spans)
    n_dev = len(raw["devices"])
    busy, running, all_gaps = [], [], []
    for dev in raw["devices"].values():
        merged = trace_reduce.merge(trace_reduce.clip(dev["ops"], lo, hi))
        busy.append(Busy(merged))
        running.append(Busy(trace_reduce.merge(trace_reduce.clip(
            [(s, e) for _, s, e in dev["modules"]], lo, hi))))
        all_gaps.extend(trace_reduce.gaps(merged, lo, hi))

    def idle_in(intervals) -> float:
        return sum(e - s - b.within(s, e) for b in busy
                   for s, e in intervals) / n_dev

    def op_gaps_in(intervals) -> float:
        return sum(r.within(s, e) - b.within(s, e)
                   for b, r in zip(busy, running)
                   for s, e in intervals) / n_dev

    table: Dict[str, dict] = {}
    for (name, _, s, e), own in zip(spans, pieces):
        row = table.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0,
                                      "idle_s": 0.0, "op_gap_s": 0.0})
        row["n"] += 1
        row["total_s"] += (e - s) / 1e9
        row["self_s"] += sum(b - a for a, b in own) / 1e9
        row["idle_s"] += idle_in(own) / 1e9
        row["op_gap_s"] += op_gaps_in(own) / 1e9

    plan_ms = (1e3 * sum(table[n]["self_s"] for n in PLAN_SPANS if n in table)
               / queries if queries else None)
    ticks = trace_reduce.merge([(s, e) for n, _, s, e in spans
                                if n == TICK_SPAN])
    tick_ns = sum(e - s for s, e in ticks)
    tick_idle = 100.0 * idle_in(ticks) / tick_ns if tick_ns else None

    dispatch = [(s, e) for n, s, e in raw["spans"]
                if n == trace_reduce.SPAN_PREFIX + "dispatch"]

    def named(inside_dispatch: bool) -> List[list]:
        out = []
        for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1]):
            if len(out) == trace_reduce.TOP:
                break
            mid = (s + e) // 2
            if inside_dispatch and not any(a <= mid < b for a, b in dispatch):
                continue
            run = max((a for n, _, a, b in spans
                       if n == RUN_SPAN and a <= mid < b), default=None)
            out.append([_name_at(spans, raw["spans"], mid), (e - s) / 1e9,
                        None if run is None else (s - run) / 1e9])
        return out

    return {"spans": dict(sorted(table.items(),
                                 key=lambda kv: -kv[1]["self_s"])),
            "idle_gaps": named(False), "dispatch_gaps": named(True),
            "plan_ms": plan_ms, "tick_idle_share": tick_idle}


def traced_cell(plan: dict, seed: int, seconds: float, *, t_start: float,
                smoke: bool = False) -> dict:
    """One traced run of the cell (``harness.run_cell``), its result line
    with the program spans reduced under ``program``."""
    import jax
    from bench import harness
    lines = harness.TRACE_LINES[jax.devices()[0].platform]
    program: dict = {}

    def after_window(run, window) -> None:
        path = trace_reduce.find_xplane(str(harness.TRACE_DIR))
        program.update(reduce(read(path), trace_reduce.read(path, **lines)))
        ctx = {"loop": plan["mix"]["loop"], "window": window,
               "queries": window["queries"], "setup_s": None}
        program["traced"] = {m["name"]: plan["readers"][m["name"]](ctx)
                             for m in plan["end_to_end"]
                             if m["name"] != "setup_s"}

    out = harness.run_cell(plan, seed, seconds, True, t_start=t_start,
                           smoke=smoke, after_window=after_window)
    checks = out.pop("checks")
    out["program"] = program
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src")]
    from bench import harness
    from repro.launch.compile_cache import setup_compile_cache
    plan = harness.cell_plan(harness.load_benchmark(), args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < plan["chips"]:
        print(f"spans: needs {plan['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = traced_cell(plan, args.seed, args.seconds, t_start=T0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
