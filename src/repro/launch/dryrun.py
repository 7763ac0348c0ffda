"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
record memory_analysis / cost_analysis / collective bytes for §Dry-run and
§Roofline.

Measurement notes (see EXPERIMENTS.md §Dry-run/Methodology):
  * XLA's HloCostAnalysis counts a while-loop body ONCE, so a production
    step built on scan-over-layers under-reports FLOPs/bytes/collectives.
    We therefore run two extra *cost-calibration* compiles per cell with
    num_layers ∈ {2, 4}, all loops unrolled (layer scan, flash-attention
    block scans, SSM chunk scan) and num_micro=1, then extrapolate
    linearly in L (exact: layers are homogeneous; L=1 is avoided because
    XLA's optimization pipeline is noisy at trivial depth — observed
    non-monotonic op counts):
        cost(L) = fixed + per_layer · L,   per_layer = (c4 − c2) / 2
    The production compile (rolled loops, real microbatching) is what must
    COMPILE — it provides memory_analysis and the collective schedule.
  * Collective bytes use ring-cost factors on the instruction result shape
    (post-SPMD per-device program): all-gather ≈ out·(n-1)/n,
    all-reduce ≈ 2·out·(n-1)/n, reduce-scatter ≈ out·(n-1),
    all-to-all ≈ out·(n-1)/n, collective-permute ≈ out.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s effective per-chip collective bandwidth

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}

_RESULT_RE = re.compile(
    r"=\s*\(?\s*(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
    r"\[([0-9,]*)\][^a-z]*([a-z][a-z0-9\-]*)\(")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def collective_stats(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives (ring-cost model), parsed from
    the post-SPMD HLO text."""
    out = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not any(op in ls for op in COLLECTIVE_OPS):
            continue
        m = _RESULT_RE.search(ls)
        if not m:
            continue
        dtype, dims, op = m.groups()
        if op.endswith("-start"):
            op = op[:-6]
        if op not in COLLECTIVE_OPS:
            continue
        res = _nbytes(dtype, dims)
        n = max(2, _group_size(ls))
        if op == "all-gather":
            b = res * (n - 1) // n
        elif op == "all-reduce":
            b = 2 * res * (n - 1) // n
        elif op == "reduce-scatter":
            b = res * (n - 1)
        elif op == "all-to-all":
            b = res * (n - 1) // n
        else:  # collective-permute
            b = res
        out[op]["count"] += 1
        out[op]["bytes"] += b
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference), N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch       # decode: 1 token/sequence


def _build(cfg, shape, mesh, *, calibrate: bool, num_micro: int,
           variant_opts=None):
    from repro.launch import steps as ST
    vo = variant_opts or {}
    if shape.kind == "train":
        step, (state_specs, batch_specs) = ST.make_train_step(
            cfg, mesh, shape, num_micro=num_micro, calibrate=calibrate,
            remat_policy=vo.get("remat_policy", "nothing"))
        return step.lower(state_specs, batch_specs)
    if shape.kind == "prefill":
        if vo.get("serve_bf16"):
            cfg = cfg.replace(param_dtype="bfloat16")
        step, (pspecs, batch_specs) = ST.make_prefill_step(
            cfg, mesh, shape, calibrate=calibrate,
            banded=vo.get("banded", False),
            seq_parallel=vo.get("seq_parallel", False),
            fsdp=not vo.get("no_fsdp", False))
        return step.lower(pspecs, batch_specs)
    if vo.get("serve_bf16"):
        cfg = cfg.replace(param_dtype="bfloat16")
    step, (pspecs, batch_specs, cache_specs) = ST.make_decode_step(
        cfg, mesh, shape, calibrate=calibrate,
        cache_shard_mode=vo.get("cache_shard", "hd"),
        per_row_write=vo.get("per_row_write", False),
        resident_weights=vo.get("resident", False))
    return step.lower(pspecs, batch_specs, cache_specs)


def _cost_of(compiled) -> dict:
    cost = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             *, num_micro: int = 4, variant_opts=None) -> dict:
    import repro.configs as C
    from repro.launch import mesh as MS
    from repro.models.config import SHAPES_BY_NAME, shape_applicable

    cfg = C.get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "ok": False}
    if not ok:
        rec.update(skipped=True, why=why, ok=True)
        return rec

    mesh = MS.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["mesh_shape"] = {k: int(v) for k, v in mesh.shape.items()}

    rec["variant"] = variant_opts or {}
    # ---- production compile: must succeed; gives memory + schedule --------
    t0 = time.time()
    lowered = _build(cfg, shape, mesh, calibrate=False, num_micro=num_micro,
                     variant_opts=variant_opts)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    sched = collective_stats(compiled.as_text())

    if mesh_kind == "multi":
        # multi-pod proves the `pod` axis shards; roofline terms are
        # single-pod only (assignment), so skip the calibration compiles.
        rec.update(
            ok=True, lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            chips=int(mesh.devices.size),
            memory_analysis={
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
            },
            collective_schedule=sched)
        return rec

    # ---- cost-calibration compiles (L=2, L=4, unrolled) -------------------
    c = {}
    for l in (2, 4):
        lw = _build(cfg.replace(num_layers=l), shape, mesh,
                    calibrate=True, num_micro=1, variant_opts=variant_opts)
        c[l] = _cost_of(lw.compile())
    L = cfg.num_layers

    def extrap(f2, f4):
        per_layer = max(0.0, (f4 - f2) / 2.0)
        fixed = max(0.0, f2 - 2.0 * per_layer)
        return fixed + per_layer * L

    flops_per_device = extrap(c[2]["flops"], c[4]["flops"])
    bytes_per_device = extrap(c[2]["bytes"], c[4]["bytes"])
    coll_by_op = {}
    for op in COLLECTIVE_OPS:
        coll_by_op[op] = int(extrap(c[2]["coll"][op]["bytes"],
                                    c[4]["coll"][op]["bytes"]))
    coll_total = sum(coll_by_op.values())

    n_chips = mesh.devices.size
    mf = model_flops(cfg, shape)
    compute_s = flops_per_device / PEAK_FLOPS_BF16
    memory_s = bytes_per_device / HBM_BW
    collective_s = coll_total / ICI_BW

    rec.update(
        ok=True,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        chips=int(n_chips),
        memory_analysis={
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
        },
        cost_analysis={"flops_per_device": flops_per_device,
                       "bytes_per_device": bytes_per_device,
                       "calib_L2": c[2], "calib_L4": c[4]},
        collective_schedule=sched,          # rolled program (body-once text)
        collective_bytes_by_op=coll_by_op,  # calibrated totals
        collective_bytes_total=coll_total,
        model_flops_total=mf,
        model_flops_per_device=mf / n_chips,
        roofline={
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "bound": max(("compute", compute_s), ("memory", memory_s),
                         ("collective", collective_s), key=lambda t: t[1])[0],
            "useful_flops_ratio": (mf / n_chips) / flops_per_device
            if flops_per_device else 0.0,
        },
    )
    return rec


def cell_path(out_dir: Path, arch: str, shape: str, mesh: str) -> Path:
    return out_dir / f"{arch}__{shape}__{mesh}.json"


def sweep(out_dir: Path, mesh_kinds, only_missing: bool = True,
          archs=None, shapes=None):
    """Run every cell in a subprocess; append-only JSON per cell."""
    import repro.configs as C
    cells = []
    for (a, s, ok, why) in C.cells(include_skipped=True):
        if archs and a not in archs:
            continue
        if shapes and s.name not in shapes:
            continue
        for mk in mesh_kinds:
            cells.append((a, s.name, mk, ok))
    print(f"sweep: {len(cells)} cells -> {out_dir}", flush=True)
    for a, sn, mk, ok in cells:
        p = cell_path(out_dir, a, sn, mk)
        if only_missing and p.exists():
            d = json.loads(p.read_text())
            if d.get("ok"):
                print(f"[skip-done] {a} {sn} {mk}", flush=True)
                continue
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
             "--shape", sn, "--mesh", mk, "--out", str(out_dir)],
            capture_output=True, text=True, timeout=7200)
        dt = time.time() - t0
        if p.exists():
            d = json.loads(p.read_text())
            if d.get("skipped"):
                status = f"SKIP ({d.get('why','')})"
            elif d.get("ok") and "roofline" not in d:
                status = f"OK   (compile {d.get('compile_s','?')}s)"
            elif d.get("ok"):
                rf = d["roofline"]
                status = (f"OK   bound={rf['bound']:10s} "
                          f"c={rf['compute_s']*1e3:9.2f}ms "
                          f"m={rf['memory_s']*1e3:9.2f}ms "
                          f"coll={rf['collective_s']*1e3:9.2f}ms")
            else:
                status = f"FAIL: {d.get('error', '')[:160]}"
        else:
            status = f"CRASH rc={r.returncode}: {(r.stderr or '')[-300:]}"
            p.write_text(json.dumps({"arch": a, "shape": sn, "mesh": mk,
                                     "ok": False,
                                     "error": f"crash rc={r.returncode}",
                                     "stderr_tail": (r.stderr or "")[-2000:]}))
        print(f"[{dt:7.1f}s] {a:20s} {sn:12s} {mk:6s} {status}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", default="", help="comma list filter for --all")
    ap.add_argument("--shapes", default="", help="comma list filter for --all")
    ap.add_argument("--num-micro", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="", help="label for variant output")
    ap.add_argument("--banded", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--cache-shard", default="hd",
                    choices=["hd", "lc", "kv", "none"])
    ap.add_argument("--per-row-write", action="store_true")
    ap.add_argument("--serve-bf16", action="store_true")
    ap.add_argument("--resident-weights", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=["nothing", "dots"])
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        sweep(out_dir, kinds, only_missing=not args.force,
              archs=[a for a in args.archs.split(",") if a] or None,
              shapes=[s for s in args.shapes.split(",") if s] or None)
        return

    assert args.arch and args.shape and args.mesh != "both"
    suffix = f"__{args.variant}" if args.variant else ""
    p = out_dir / f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json"
    variant_opts = None
    if args.variant:
        variant_opts = {"banded": args.banded,
                        "seq_parallel": args.seq_parallel,
                        "cache_shard": args.cache_shard,
                        "per_row_write": args.per_row_write,
                        "serve_bf16": args.serve_bf16,
                        "resident": args.resident_weights,
                        "no_fsdp": args.no_fsdp,
                        "remat_policy": args.remat_policy}
    try:
        rec = run_cell(args.arch, args.shape, args.mesh,
                       num_micro=args.num_micro, variant_opts=variant_opts)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    p.write_text(json.dumps(rec, indent=2))
    if rec.get("ok") and not rec.get("skipped"):
        keys = [k for k in ("arch", "shape", "mesh", "compile_s",
                            "memory_analysis", "collective_bytes_by_op",
                            "roofline") if k in rec]
        print(json.dumps({k: rec[k] for k in keys}, indent=2))
    else:
        print(json.dumps(rec, indent=2)[:2000])
        if not rec.get("ok"):
            sys.exit(1)


if __name__ == "__main__":
    # Set only when run as a program, before jax is first imported (every
    # jax import above is inside a function): 512 virtual CPU devices for
    # the meshes, and the process pinned to the CPU.  Importing this module
    # leaves the caller's XLA_FLAGS and platform alone.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    main()
