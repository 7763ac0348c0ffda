#!/usr/bin/env python3
"""Readings that set a cell's limits: for each seed, one run of the cell
(a short window at the cell's own load) whose served requests are compared
with the float32 reference, and the float8 reference put in the program's
place at the same positions (the control).  One process, one line each.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds a,b,c

Not part of the benchmark's own runs.  Needs the TPU as ``run.py`` does.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch.compile_cache import setup_compile_cache
    import jax
    plan = harness.cell_plan(harness.load_benchmark(), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < plan["chips"]:
        print("control: needs the TPU", file=sys.stderr)
        return 1
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        out = harness.run_cell(plan, seed, args.seconds, False, t_start=t,
                               control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "readings": out["control"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
