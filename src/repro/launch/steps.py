"""Step builders: jitted train / prefill / decode steps with explicit
in/out shardings, shared by the dry-run, the trainer, and the serving
engine.

Each builder returns (jitted_fn, in_specs, in_shardings) so callers can
either execute it or `.lower(*specs).compile()` it (dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import common as CC
from repro.launch import mesh as MS
from repro.models import layers as LY
from repro.models import mamba as MB
from repro.models import model as MDL
from repro.models import moe as MOE
from repro.models.config import ModelConfig, ShapeSpec
from repro.training import optim as OPT

PyTree = Any


def calibration_fns(seq_len: int, banded: bool = False):
    """Unrolled attention/scan variants so XLA's HloCostAnalysis counts
    every iteration (used by the dry-run's L∈{2,4} cost-calibration
    compiles; production steps keep rolled loops + small blocks). Banded
    variants calibrate with 2048 blocks so the band ratio is resolvable."""
    blk = 2048 if banded else min(4096, max(512, seq_len))
    attn_fn = functools.partial(LY.flash_attention, block_q=blk,
                                block_kv=blk, unroll=True, banded=banded)
    scan_fn = functools.partial(MB.selective_scan, chunk=2048, unroll=True)
    return attn_fn, scan_fn


def _named(mesh: Mesh, tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def train_state_specs(cfg: ModelConfig) -> Dict[str, Any]:
    ps = MDL.param_specs(cfg)
    f32 = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), t)
    return {"params": ps, "opt": {"m": f32(ps), "v": f32(ps)},
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def train_state_pspecs(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = True
                       ) -> Dict[str, Any]:
    pp = MS.param_pspecs(cfg, mesh, fsdp=fsdp)
    return {"params": pp, "opt": {"m": pp, "v": pp}, "step": P()}


def init_train_state(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    params = MDL.init_params(cfg, key)
    return {"params": params, "opt": OPT.init_opt_state(params),
            "step": jnp.int32(0)}


# ------------------------------- train step -----------------------------------
def make_train_step(cfg: ModelConfig, mesh: Optional[Mesh], shape: ShapeSpec,
                    *, num_micro: int = 1, opt_cfg: OPT.AdamWConfig = None,
                    remat: bool = True, donate: bool = True,
                    calibrate: bool = False, remat_policy: str = "nothing"):
    """Returns (jitted step, (state_specs, batch_specs))."""
    opt_cfg = opt_cfg or OPT.AdamWConfig()
    attn_fn = scan_fn = None
    unroll_layers = False
    if calibrate:
        attn_fn, scan_fn = calibration_fns(shape.seq_len)
        unroll_layers = True
        num_micro = 1
    batch_specs = CC.train_batch_specs(cfg, shape.global_batch, shape.seq_len)
    if mesh is not None:
        da = MS.data_axes(mesh)
        logits_cs = MS.logits_constraint(cfg, mesh, True)
        micro_cs = lambda t: jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, da, *([None] * (x.ndim - 2))))), t)
    else:
        logits_cs = micro_cs = MOE.Identity

    def loss_fn(params, mb):
        logits, _ = MDL.forward(cfg, params, mb, mode="train", remat=remat,
                                logits_cs=logits_cs,
                                attn_fn=attn_fn, scan_fn=scan_fn,
                                unroll_layers=unroll_layers,
                                remat_policy=remat_policy)
        return MDL.lm_loss(cfg, logits, mb["labels"], mb["mask"])

    def train_step(state, batch):
        params = state["params"]
        if num_micro == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            micro = {k: v.reshape((num_micro, v.shape[0] // num_micro)
                                  + v.shape[1:]) for k, v in batch.items()}
            micro = micro_cs(micro)

            def acc(carry, mb):
                lsum, gsum = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (lsum + l, jax.tree.map(jnp.add, gsum, g)), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (lsum, gsum), _ = jax.lax.scan(acc, (jnp.float32(0), g0), micro)
            loss = lsum / num_micro
            grads = jax.tree.map(lambda g: g / num_micro, gsum)
        new_params, new_opt, stats = OPT.adamw_update(
            opt_cfg, params, grads, state["opt"], state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, **stats}

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,) if donate else ()), \
            (train_state_specs(cfg), batch_specs)

    state_sh = _named(mesh, train_state_pspecs(cfg, mesh))
    batch_sh = _named(mesh, MS.batch_pspecs(cfg, mesh, batch_specs))
    metric_sh = {k: NamedSharding(mesh, P()) for k in
                 ("loss", "grad_norm", "lr")}
    step = jax.jit(train_step,
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, metric_sh),
                   donate_argnums=(0,) if donate else ())
    return step, (train_state_specs(cfg), batch_specs)


# ------------------------------ prefill step ----------------------------------
def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh], shape: ShapeSpec,
                      *, cache_len: Optional[int] = None,
                      emit_cache: bool = True, calibrate: bool = False,
                      banded: bool = False, seq_parallel: bool = False,
                      fsdp: bool = True):
    """Prefill: full-sequence forward → (last-token logits, decode cache).

    banded        — §Perf opt A: sliding-window flash skips out-of-window
                    kv blocks (SWA archs only).
    seq_parallel  — §Perf opt C: sequence over `model`, ZeRO-3 weights."""
    attn_fn = scan_fn = None
    unroll_layers = False
    if calibrate:
        attn_fn, scan_fn = calibration_fns(shape.seq_len, banded=banded)
        unroll_layers = True
    elif banded:
        attn_fn = functools.partial(LY.flash_attention, banded=True)
    batch_specs = CC.prefill_batch_specs(cfg, shape.global_batch, shape.seq_len)
    cache_len = cache_len or shape.seq_len
    residual_cs = kv_cs = MOE.Identity
    if mesh is not None and seq_parallel:
        residual_cs, kv_cs = MS.seq_parallel_hooks(mesh)

    def prefill_step(params, batch):
        cache = MDL.init_cache(cfg, shape.global_batch, cache_len) \
            if (emit_cache and cfg.supports_decode) else None
        logits, new_cache = MDL.forward(
            cfg, params, batch, mode=("prefill" if cache is not None else "train"),
            cache=cache, remat=False, last_only=cfg.supports_decode,
            attn_fn=attn_fn, scan_fn=scan_fn, unroll_layers=unroll_layers,
            residual_cs=residual_cs, kv_cs=kv_cs)
        return logits[:, -1], new_cache

    if mesh is None:
        return jax.jit(prefill_step), (MDL.param_specs(cfg), batch_specs)

    pp = MS.param_pspecs_zero3(cfg, mesh) if seq_parallel else \
        MS.param_pspecs(cfg, mesh, fsdp=fsdp)
    param_sh = _named(mesh, pp)
    batch_sh = _named(mesh, MS.batch_pspecs(cfg, mesh, batch_specs))
    da = MS.data_axes(mesh)
    logit_sh = NamedSharding(mesh, P(da, "model")) if not seq_parallel \
        else NamedSharding(mesh, P(da, None))
    cache_sh = None
    if emit_cache and cfg.supports_decode:
        cache_sh = _named(mesh, MS.cache_pspecs(
            cfg, mesh, MDL.cache_specs(cfg, shape.global_batch, cache_len)))
    step = jax.jit(prefill_step,
                   in_shardings=(param_sh, batch_sh),
                   out_shardings=(logit_sh, cache_sh))
    return step, (MDL.param_specs(cfg), batch_specs)


# ------------------------------ decode step -----------------------------------
def make_decode_step(cfg: ModelConfig, mesh: Optional[Mesh], shape: ShapeSpec,
                     *, cache_shard_mode: str = "hd", donate_cache: bool = True,
                     calibrate: bool = False, per_row_write: bool = False,
                     resident_weights: bool = False):
    """One-token serve_step against a seq_len-deep cache.

    cache_shard_mode='lc' + per_row_write=True is §Perf opt B: cache length
    sharded over `model` (softmax partials → tiny collectives) with the
    slot write as a masked elementwise update (no DUS on a sharded dim)."""
    assert cfg.supports_decode, f"{cfg.name} has no decode step"
    batch_specs = CC.decode_batch_specs(cfg, shape.global_batch)
    cache_specs = MDL.cache_specs(cfg, shape.global_batch, shape.seq_len,
                                  include_row_idx=per_row_write)

    def decode_step(params, batch, cache):
        logits, new_cache = MDL.forward(
            cfg, params, batch, mode="decode", cache=cache, remat=False,
            unroll_layers=calibrate)
        return logits, new_cache

    if mesh is None:
        return jax.jit(decode_step,
                       donate_argnums=(2,) if donate_cache else ()), \
            (MDL.param_specs(cfg), batch_specs, cache_specs)

    if cache_shard_mode == "hd" and cfg.head_dim % 16 == 0:
        attn_mode = "hd"
    elif cache_shard_mode == "lc":
        attn_mode = "replicated"    # model axis belongs to cache length
    else:
        attn_mode = "heads"
    param_sh = _named(mesh, MS.param_pspecs(cfg, mesh,
                                            fsdp=not resident_weights,
                                            attn_mode=attn_mode,
                                            resident=resident_weights))
    batch_sh = _named(mesh, MS.batch_pspecs(cfg, mesh, batch_specs))
    cache_sh = _named(mesh, MS.cache_pspecs(cfg, mesh, cache_specs,
                                            shard_mode=cache_shard_mode))
    da = MS.data_axes(mesh)
    b = shape.global_batch
    bd = MS.axis_size(mesh, da)
    logit_sh = NamedSharding(
        mesh, P(da if (b % bd == 0 and b >= bd) else None, None, "model"))
    step = jax.jit(decode_step,
                   in_shardings=(param_sh, batch_sh, cache_sh),
                   out_shardings=(logit_sh, cache_sh),
                   donate_argnums=(2,) if donate_cache else ())
    return step, (MDL.param_specs(cfg), batch_specs, cache_specs)
