#!/usr/bin/env python3
"""Bring-up check: semantic SQL served by OLMo-1B at its published widths on
one TPU chip, through the entry points a user calls.

    python3 chip_smoke.py              # one TPU chip; non-zero exit elsewhere
    python3 chip_smoke.py --rehearse   # the same phases at the smoke config

The chain is ``IPDB.sql`` -> ``PredictOperator`` -> ``InferenceService`` ->
``JaxExecutor`` -> ``InferenceEngine`` / ``ContinuousBatcher``.  Table,
weights and prompts are made from ``--seed``.  First the Pallas kernels
that compile for the chip are checked against their jnp references on small
inputs at OLMo-1B's head width.  Then a semantic projection and a
semantic select run under the dense and then the paged KV layout, each once
cold (compiles included) and once warm (prompt cache emptied, so the model
answers every row again).  Every input row must come back from the
projection and be judged by the select, no call may fail, and every value
must have its schema's type; any failure exits non-zero.  The last line of
a chip run is ``{"ok": true, "device": {...}}``; ``--rehearse`` never
prints it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "olmo-1b"
LAYOUTS = ("dense", "paged")
ROWS = 64
ROWS_PER_PROMPT = 4
# the dense layout decodes after its power-of-two prefill bucket: a 4-row
# prompt buckets to 1024 tokens and its answer must still fit
MAX_LEN = 2048
ADJ = ["sturdy", "flimsy", "quiet", "noisy", "cheap", "overpriced", "fast",
       "sluggish", "bright", "dim", "compact", "bulky"]
NOUN = ["kettle", "headset", "keyboard", "monitor", "router", "blender",
        "charger", "backpack", "lamp", "speaker", "tripod", "drill"]
VERB = ["broke after a week", "works as advertised", "arrived scratched",
        "exceeded my expectations", "stopped charging", "is worth the price",
        "feels premium", "was returned", "overheats quickly",
        "is easy to set up"]
QUERIES = {
    # semantic projection: one VARCHAR per row, every row returned
    "projection": ("SELECT id, LLM m (PROMPT 'name the {topic VARCHAR} of "
                   "this review: {{review}}') AS topic FROM Reviews"),
    # semantic select: every row judged, the TRUE ones returned
    "select": ("SELECT id, product FROM Reviews WHERE LLM m (PROMPT 'is "
               "this review {positive BOOLEAN}: {{review}}') = TRUE"),
}
# ExecStats counters that must stay zero: any of them means a call failed
FAILURE_COUNTERS = ("retries", "batch_fallbacks", "transient_retries",
                    "deadline_drops", "backend_timeouts",
                    "breaker_rejections")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_table(seed: int, n: int):
    import numpy as np
    from repro.relational.table import Table
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        noun = NOUN[rng.integers(len(NOUN))]
        rows.append({
            "id": i,
            "product": f"{ADJ[rng.integers(len(ADJ))]} {noun}",
            "review": f"The {noun} {VERB[rng.integers(len(VERB))]} and "
                      f"{VERB[rng.integers(len(VERB))]}.",
        })
    return Table.from_rows(rows, {"id": "INTEGER", "product": "VARCHAR",
                                  "review": "VARCHAR"})


def check_types(t) -> None:
    """Every value of the result has its column's schema type."""
    import numpy as np
    for col, typ in t.schema.items():
        vals = t.cols[col]
        if typ == "BOOLEAN":
            ok = vals.dtype == np.bool_
        elif typ == "INTEGER":
            ok = np.issubdtype(vals.dtype, np.integer)
        elif typ == "VARCHAR":
            ok = all(isinstance(v, str) for v in vals)
        else:
            raise AssertionError(f"unexpected column type {col} {typ}")
        if not ok:
            raise AssertionError(f"column {col} is not all {typ}: "
                                 f"{list(vals)[:8]}")


def check_result(name: str, r, n_rows: int) -> None:
    s = r.stats
    bad = {c: getattr(s, c) for c in FAILURE_COUNTERS if getattr(s, c)}
    if bad or s.cancelled:
        raise AssertionError(f"{name}: failed calls {bad} "
                             f"cancelled={s.cancelled}")
    if s.llm_calls == 0 or s.decode_tokens == 0:
        raise AssertionError(f"{name}: the model was not called "
                             f"(calls={s.llm_calls}, "
                             f"decode_tokens={s.decode_tokens})")
    check_types(r.table)
    ids = [int(x) for x in r.table.cols["id"]]
    if len(set(ids)) != len(ids) or not set(ids) <= set(range(n_rows)):
        raise AssertionError(f"{name}: ids out of range or repeated: {ids}")
    if name == "projection" and len(ids) != n_rows:
        raise AssertionError(f"projection returned {len(ids)} of "
                             f"{n_rows} rows")
    if name == "select" and s.rows_predicted < n_rows:
        raise AssertionError(f"select judged {s.rows_predicted} of "
                             f"{n_rows} rows")


def check_kernels(cfg, seed: int) -> None:
    """The Pallas kernels that compile for the chip (none is on the served
    path yet) agree with the repository's jnp references on small inputs at
    the model's head width.  References run at the highest matmul precision;
    the tolerance is the kernel tests' bf16 one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.models import layers as L

    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, dtype)

    # decode: two rows over a 1024-slot cache, ragged fills
    B, LEN, PS, NB, P = 2, 1024, 64, 16, 32
    fill = jnp.asarray([700, 512], jnp.int32)
    qpos = fill - 1
    q, kc, vc = normal((B, H, D)), normal((B, LEN, KV, D)), \
        normal((B, LEN, KV, D))
    slot = jnp.arange(LEN, dtype=jnp.int32)[None]
    spos = jnp.where(slot < fill[:, None], slot, -1)
    # paged: the rows' pages scattered over the pool, even pages frozen
    kp, vp = normal((KV, P, PS, D)), normal((KV, P, PS, D))
    pages = jax.random.permutation(next(keys), P).reshape(B, NB)
    bt = jnp.where(jnp.arange(NB)[None] * PS < fill[:, None], pages, -1)

    def int8(pool):
        scale = jnp.max(jnp.abs(pool.astype(jnp.float32)), axis=(2, 3)) / 127
        return jnp.round(pool / scale[..., None, None]).astype(jnp.int8), \
            scale

    (kq, ks), (vq, vs) = int8(kp), int8(vp)
    quant = {"kq": kq, "vq": vq, "kscale": ks, "vscale": vs,
             "flags": (jnp.arange(P) % 2 == 0).astype(jnp.int32)}
    # prefill: one causal 512-token sequence
    S = 512
    fq, fk, fv = normal((1, S, H, D)), normal((1, S, KV, D)), \
        normal((1, S, KV, D))
    fpos = jnp.arange(S, dtype=jnp.int32)[None]
    # grouped matmul: ragged groups, one empty
    gx, gw = normal((256, 512)), normal((4, 512, 256)) * 0.05
    gs = jnp.asarray([100, 0, 60, 96], jnp.int32)
    # sampler: the padded vocabulary, a sparse mask, Gumbel noise
    V = cfg.padded_vocab
    logits = normal((8, V), jnp.float32)
    mask = jax.random.uniform(next(keys), (8, V)) > 0.6
    noise = jax.random.gumbel(next(keys), (8, V))

    cases = {
        "decode_dense": (
            lambda: ops.decode_attention(q, kc, vc, spos, qpos),
            lambda: L.decode_attention(q, kc, vc, spos, qpos)),
        "decode_paged": (
            lambda: ops.decode_attention_paged(q, kp, vp, bt, qpos),
            lambda: L.decode_attention_paged(q, kp, vp, bt, qpos)),
        "decode_paged_quant": (
            lambda: ops.decode_attention_paged(q, kp, vp, bt, qpos,
                                               quant=quant),
            lambda: L.decode_attention_paged(q, kp, vp, bt, qpos,
                                             quant=quant)),
        "flash": (
            lambda: ops.flash_attention(fq, fk, fv, fpos, fpos),
            lambda: L.reference_attention(fq, fk, fv, fpos, fpos)),
        "gmm": (lambda: ops.gmm(gx, gw, gs),
                lambda: ref.gmm_ref(gx, gw, gs)),
        "sampler": (
            lambda: ops.constrained_sample(logits, mask, noise, block_v=256),
            lambda: ref.constrained_sample_ref(logits, mask, noise)),
    }
    tol = 3e-2
    for name, (kernel, reference) in cases.items():
        got = np.asarray(kernel(), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(), np.float32)
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            raise AssertionError(f"kernel {name}: shape {got.shape} against "
                                 f"{want.shape}, or non-finite values")
        err = float(np.max(np.abs(got - want)))
        if name == "sampler":
            ok = err == 0            # the same token, exactly
        else:
            ok = np.allclose(got, want, atol=tol, rtol=tol)
        log(f"kernel {name}: max |kernel - reference| = {err:.6g}")
        if not ok:
            raise AssertionError(f"kernel {name} disagrees with its "
                                 f"reference (max abs error {err:.6g})")


class CompileClock:
    """Sums XLA backend compile time and count (a persistent-cache load
    counts too), read per phase, and keeps each program's time by name."""

    def __init__(self):
        import jax
        self.secs, self.count = 0.0, 0
        self.programs = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, fun_name: str = "?", **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1
            self.programs.append((secs, fun_name))

    def read(self):
        return self.secs, self.count

    def slowest(self, since: int, n: int = 4) -> str:
        top = sorted(self.programs[since:], reverse=True)[:n]
        return ", ".join(f"{name} {secs:.3f} s" for secs, name in top)


def run_layout(layout: str, table, clock, model_opts: str):
    from repro.core.database import IPDB
    from repro.core.predict import PromptCache
    db = IPDB(session_options={"enable_pilot": False})
    db.register_table("Reviews", table)
    db.sql(f"CREATE LLM MODEL m PATH 'jax:{ARCH}' ON PROMPT OPTIONS "
           f"{{ {model_opts}, 'kv_layout': '{layout}' }}")
    out = {}
    for name, q in QUERIES.items():
        for phase in ("cold", "warm"):
            db.prompt_cache = PromptCache()     # the model answers each row
            c0, n0 = clock.read()
            t0 = time.perf_counter()
            r = db.sql(q)
            wall = time.perf_counter() - t0
            c1, n1 = clock.read()
            check_result(name, r, len(table))
            s = r.stats
            rec = {"wall_s": wall, "compile_s": c1 - c0,
                   "compiles": n1 - n0, "rows_out": len(r.table),
                   "calls": s.llm_calls, "prefill_tokens": s.prefill_tokens,
                   "decode_tokens": s.decode_tokens}
            out[f"{name}/{phase}"] = rec
            log(f"{layout:5s} {name:10s} {phase}: {wall:.3f} s "
                f"(compile {rec['compile_s']:.3f} s in {rec['compiles']} "
                f"programs) rows_in={len(table)} rows_out={rec['rows_out']} "
                f"calls={s.llm_calls} prefill_tokens={s.prefill_tokens} "
                f"decode_tokens={s.decode_tokens} failed_calls=0")
    db.close()
    return out


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same phases at the smoke config on any "
                         "backend; never reports ok")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the table and the random weights")
    args = ap.parse_args(argv)

    try:
        import repro.configs as C
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing ({e})",
              file=sys.stderr)
        return 2
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); "
              f"use --rehearse for a run without the chip", file=sys.stderr)
        return 1
    cache_dir = setup_compile_cache()
    dev = jax.devices()[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} compile_cache={cache_dir}")

    cfg = C.get_smoke_config(ARCH) if args.rehearse else C.get_config(ARCH)
    log(f"config {ARCH}{' (smoke)' if args.rehearse else ''}: "
        f"layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
        f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={259 if args.rehearse else cfg.vocab_size} "
        f"param_dtype={cfg.param_dtype} compute_dtype={cfg.compute_dtype}")
    model_opts = (f"'smoke': {1 if args.rehearse else 0}, "
                  f"'seed': {args.seed}, 'max_len': {MAX_LEN}, "
                  f"'batch_size': {ROWS_PER_PROMPT}, 'num_slots': 8, "
                  f"'max_str': 12, 'temperature': 0")

    clock = CompileClock()
    t0 = time.perf_counter()
    check_kernels(C.get_config(ARCH), args.seed)
    log(f"kernels agree with their references "
        f"({time.perf_counter() - t0:.3f} s)")

    t0 = time.perf_counter()
    table = make_table(args.seed, ROWS)
    log(f"table Reviews: {len(table)} rows from seed {args.seed} "
        f"({time.perf_counter() - t0:.3f} s)")

    for layout in LAYOUTS:
        t0 = time.perf_counter()
        first_program = len(clock.programs)
        res = run_layout(layout, table, clock, model_opts)
        total = time.perf_counter() - t0
        cold = sum(v["wall_s"] for k, v in res.items() if k.endswith("cold"))
        warm = sum(v["wall_s"] for k, v in res.items() if k.endswith("warm"))
        comp = sum(v["compile_s"] for v in res.values())
        log(f"{layout:5s} summary: total {total:.3f} s, cold queries "
            f"{cold:.3f} s, warm queries {warm:.3f} s, compile {comp:.3f} s, "
            f"peak_bytes_in_use={peak_bytes(dev)}")
        log(f"{layout:5s} slowest compiles: "
            f"{clock.slowest(first_program)}")
        gc.collect()                    # free this layout's weights and KV

    log(f"peak_bytes_in_use={peak_bytes(dev)}")
    if args.rehearse:
        log("rehearsal passed (no chip: not reporting ok)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
