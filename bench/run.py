#!/usr/bin/env python3
"""Run one cell of the benchmark on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and, last, each compared number beside its limit on
standard error; the last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import harness
        import repro.core.database  # noqa: F401  (the program under test)
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"bench: the program is missing ({e})", file=sys.stderr)
        return 2
    plan = harness.cell_plan(harness.load_benchmark(), args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < plan["chips"]:
        print(f"bench: needs {plan['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    cache = setup_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # the first run of a cell in this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log(f"{args.workload} seed={args.seed} seconds={args.seconds} "
                f"trace={args.trace} device={devs[0].device_kind} "
                f"x{len(devs)} compile_cache={cache}")
    out = harness.run_cell(plan, args.seed, args.seconds, bool(args.trace),
                           t_start=T0)
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
