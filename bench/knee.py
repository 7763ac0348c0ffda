#!/usr/bin/env python3
"""Sweep of open-loop rates for a lookup cell, to find the knee once: for
each rate, one window of lookups sent at that rate, with its 90th-percentile
latency, its backlog (queries due in the window still unfinished when it
closed) and how late the generator ran.  One process, one line each.

    python3 bench/knee.py --workload <cell> --seconds <s> --rates 0.5,1,1.5

Not part of the benchmark's own runs.  Needs the TPU as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import harness, readers, traffic
    from repro.launch.compile_cache import setup_compile_cache
    from repro.relational.table import Table
    import jax
    plan = harness.cell_plan(harness.load_benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        print("knee: needs the TPU", file=sys.stderr)
        return 1
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    mix = plan["mix"]
    run = harness.build(plan, args.seed, args.seconds, traced=False)
    harness.warm_up(run)
    tab = mix["table"]
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix["open"]["rate_qps"] = rate
        seen = set(run.text_ids)
        sched = traffic.open_loop_schedule(mix, args.seed + 1 + k,
                                           args.seconds, seen)
        run.schedule = sched
        run.text_ids = {r[tab["text_column"]]: r[tab["key"]]
                        for r in sched["rows"]}
        run.db.register_table(tab["name"], Table.from_rows(
            sched["rows"], traffic.schema_of(tab)))
        w = harness.open_window(run, args.seconds)
        lat = [(q["end"] - q["due"]) if q["end"] and not q["error"]
               else float("inf") for q in w["queries"]]
        backlog = sum(1 for q in w["queries"]
                      if q["end"] is None or q["end"] > w["close"])
        print(json.dumps({"rate_qps": rate, "queries": len(lat),
                          "query_p50_s": readers.nearest_rank(lat, 50),
                          "query_p90_s": readers.nearest_rank(lat, 90),
                          "backlog_at_close": backlog,
                          "failed": sum(1 for q in w["queries"]
                                        if q["error"]),
                          "late_p90_s": float(np.percentile(w["late"], 90)),
                          "late_max_s": float(max(w["late"]))}), flush=True)
    harness.free_program(run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
