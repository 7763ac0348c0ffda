"""Production mesh + sharding rules.

`make_production_mesh` is a FUNCTION (importing this module never touches
jax device state). Axes:
  single-pod : (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     — 512 chips across DCN

Sharding strategy (baseline; §Perf hillclimbs deviate per-cell):
  * training  = 2D FSDP×TP: weight contraction dims shard over `data`
    (+`pod`), feature dims over `model`; optimizer state like weights.
  * serving   = same weight layout (weight-stationary 2D TP for decode —
    activations are small, so resharding them is cheaper than gathering
    weights).
  * attention = query heads over `model` when num_heads%16==0 (whole-head
    blocks stay within GQA groups); otherwise attention weights replicate
    over `model` and FFN/vocab carry the model axis (hymba-25H, paligemma-8H).
  * KV cache  = batch over `data`; head_dim over `model` (uniform across
    archs — head_dim is always divisible; avoids DUS on a sharded dim).
    long_500k (batch=1) shards the cache length axis over `data` instead.
  * MoE       = experts over `model` when num_experts%16==0 (EP, all-to-all
    dispatch via sharding constraints), else per-expert FFN TP (mixtral).
  * vocab     = always padded to a multiple of 256 → shards over `model`.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import model as MDL
from repro.models.config import ENCODER, VLM, ModelConfig

PyTree = Any


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def data_axes(mesh: Mesh):
    """The (possibly compound) batch-sharding axis."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]


# ----------------------------- parameter specs --------------------------------
def param_pspecs(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = True,
                 attn_mode: str = "heads", resident: bool = False) -> PyTree:
    """PartitionSpec tree matching model.param_specs(cfg).

    attn_mode:
      'heads'      — query heads over `model` when divisible (train/prefill);
      'hd'         — head_dim over `model` for all attention tensors (decode:
                     uniform across archs, matches the hd-sharded KV cache);
      'replicated' — attention weights carry no model-axis sharding (used
                     with length-sharded caches, §Perf opt B — the model
                     axis belongs to the cache length there).

    resident=True (serving decode, §Perf opt B'): weights stay sharded on
    device across steps — feature dims spread over BOTH mesh axes when they
    divide, and nothing is sharded on a dim that would force a per-step
    weight all-gather. Activations (tiny at decode) reshard instead.
    """
    da = data_axes(mesh)
    fa = da if fsdp else None          # fsdp axis (contraction dims)
    mdl = "model"
    heads_tp = cfg.heads_shardable and attn_mode == "heads"
    hd_tp = attn_mode == "hd" and cfg.head_dim % 16 == 0

    bd = axis_size(mesh, da)
    both = tuple(da) + (mdl,)
    nboth = bd * mesh.shape[mdl]

    def wide(dim: int):
        # widest axis set dividing `dim` (for resident layouts)
        if dim % nboth == 0:
            return both
        if dim % mesh.shape[mdl] == 0:
            return mdl
        if dim % bd == 0:
            return da
        return None

    if resident:
        fa = None

    def spec_for(path: str, ndim_core: int) -> P:
        # vectors (norm scales, biases over d_model / dt / conv)
        if path.endswith((".scale", ".bias")):
            return P(*( [None] * ndim_core ))
        if ".attn.wq" in path or ".attn.wk" in path or ".attn.wv" in path:
            # (M, H|KV, hd)
            if hd_tp:
                return P(fa, None, mdl)
            if ".attn.wq" in path and heads_tp:
                return P(fa, mdl, None)
            return P(fa, None, None)           # KV replicated / odd heads
        if ".attn.wo" in path:
            if hd_tp:
                return P(None, mdl, fa)
            return P(mdl, None, fa) if heads_tp else P(None, None, fa)
        if ".attn.b" in path:
            if hd_tp:
                return P(None, mdl)
            return P(mdl, None) if (heads_tp and ".bq" in path) else P(None, None)
        if ".mlp.w_gate" in path or ".mlp.w_up" in path or ".mlp.w_in" in path:
            return P(None, wide(cfg.d_ff)) if resident else P(fa, mdl)
        if ".mlp.w_down" in path or ".mlp.w_out" in path:
            return P(wide(cfg.d_ff), None) if resident else P(mdl, fa)
        if ".mlp.b_in" in path:
            return P(mdl)
        if ".mlp.b_out" in path:
            return P(None)
        if ".moe.router" in path:
            return P(fa, None)
        if ".moe.w_gate" in path or ".moe.w_up" in path:
            # (E, M, F)
            if resident:
                fdim = da if cfg.d_ff % bd == 0 else None
                return P(mdl, None, fdim) if cfg.expert_sharding == "ep" \
                    else P(None, None, wide(cfg.d_ff))
            return P(mdl, fa, None) if cfg.expert_sharding == "ep" \
                else P(None, fa, mdl)
        if ".moe.w_down" in path:
            # (E, F, M)
            if resident:
                fdim = da if cfg.d_ff % bd == 0 else None
                return P(mdl, fdim, None) if cfg.expert_sharding == "ep" \
                    else P(None, wide(cfg.d_ff), None)
            return P(mdl, None, fa) if cfg.expert_sharding == "ep" \
                else P(None, mdl, fa)
        if ".ssm.in_x" in path or ".ssm.in_z" in path:
            return P(fa, mdl)
        if ".ssm.conv_w" in path:
            return P(None, mdl)
        if ".ssm.conv_b" in path or ".ssm.dt_bias" in path or "ssm.D" in path:
            return P(mdl)
        if ".ssm.x_proj" in path:
            return P(mdl, None)
        if ".ssm.dt_proj" in path:
            return P(None, mdl)
        if ".ssm.A_log" in path:
            return P(mdl, None)
        if ".ssm.out_proj" in path:
            return P(mdl, fa)
        if "embed" in path:
            if resident:
                return P(wide(cfg.padded_vocab), None)
            return P(mdl, fa)                  # (Vp, M)
        if "lm_head" in path:
            if resident:
                return P(None, wide(cfg.padded_vocab))
            return P(fa, mdl)                  # (M, Vp)
        raise ValueError(f"no sharding rule for {path}")

    specs = MDL.param_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    out = []
    for path, leaf in flat:
        pstr = jax.tree_util.keystr(path).replace("'", "").replace("[", ".") \
            .replace("]", "")
        stacked = ".layers." in pstr
        core = len(leaf.shape) - (1 if stacked else 0)
        sp = spec_for(pstr, core)
        if stacked:
            sp = P(None, *sp)                  # leading layer-stack axis
        out.append(sp)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------ batch/cache specs ------------------------------
def batch_pspecs(cfg: ModelConfig, mesh: Mesh, batch_specs: Dict[str, Any]) -> Dict[str, Any]:
    da = data_axes(mesh)
    bd = axis_size(mesh, da)
    out = {}
    for k, v in batch_specs.items():
        b = v.shape[0]
        lead = da if b % bd == 0 and b >= bd else None
        out[k] = P(lead, *([None] * (len(v.shape) - 1)))
    return out


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_specs: Dict[str, Any],
                 *, shard_mode: str = "hd") -> Dict[str, Any]:
    """shard_mode: 'hd' (head_dim over model), 'lc' (cache length over
    model), 'kv' (kv heads over model), 'none'. Batch=1 cells fall back to
    sharding the length axis over `data`."""
    da = data_axes(mesh)
    bd = axis_size(mesh, da)
    out: Dict[str, Any] = {}
    for k, v in cache_specs.items():
        if k == "idx":
            out[k] = P()
            continue
        if k == "row_idx":                       # (B,)
            b = v.shape[0]
            out[k] = P(da if (b % bd == 0 and b >= bd) else None)
            continue
        if k == "slot_pos":                      # (B, lc)
            b, lc = v.shape
            if b % bd == 0 and b >= bd:
                out[k] = P(da, None)
            elif lc % bd == 0:
                out[k] = P(None, da)
            else:
                out[k] = P(None, None)
            continue
        if k in ("k", "v"):                      # (L, B, lc, KV, hd)
            _, b, lc, kvh, hd = v.shape
            bspec = da if (b % bd == 0 and b >= bd) else None
            lspec = None if bspec is not None else (da if lc % bd == 0 else None)
            kspec, hspec = None, None
            if shard_mode == "kv" and kvh % 16 == 0:
                kspec = "model"
            elif shard_mode == "lc" and lc % 16 == 0:
                lspec = (lspec, "model") if lspec else "model"
            elif shard_mode == "hd" and hd % 16 == 0:
                hspec = "model"
            out[k] = P(None, bspec, lspec, kspec, hspec)
            continue
        if k == "conv":                          # (L, B, K-1, Di)
            _, b, _, di = v.shape
            bspec = da if (b % bd == 0 and b >= bd) else None
            out[k] = P(None, bspec, None, "model" if di % 16 == 0 else None)
            continue
        if k == "h":                             # (L, B, Di, N)
            _, b, di, _ = v.shape
            bspec = da if (b % bd == 0 and b >= bd) else None
            out[k] = P(None, bspec, "model" if di % 16 == 0 else None, None)
            continue
        raise ValueError(k)
    return out


# --------------------------- activation constraints ----------------------------
def logits_constraint(cfg: ModelConfig, mesh: Mesh, batch_shardable: bool):
    da = data_axes(mesh)
    spec = P(da if batch_shardable else None, None, "model")

    def f(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return f


# --------------------------- ZeRO-3 / sequence parallel -----------------------
def param_pspecs_zero3(cfg: ModelConfig, mesh: Mesh) -> PyTree:
    """ZeRO-3 layout for sequence-parallel prefill (§Perf opt C): every
    weight leaf is flat-sharded on its largest divisible dim over as many
    axes as divide it; weights are all-gathered per layer at use while
    activations stay (batch × sequence)-sharded."""
    da = data_axes(mesh)
    bd = axis_size(mesh, da)
    md = mesh.shape["model"]
    candidates = [tuple(da) + ("model",), tuple(da), ("model",)]
    sizes = [bd * md, bd, md]

    def leaf_spec(shape, stacked):
        core = list(shape[1:] if stacked else shape)
        order = sorted(range(len(core)), key=lambda i: -core[i])
        for cand, n in zip(candidates, sizes):
            for d in order:
                if core[d] % n == 0:
                    sp = [None] * len(core)
                    sp[d] = cand if len(cand) > 1 else cand[0]
                    return P(*( [None] + sp if stacked else sp ))
        return P(*([None] * len(shape)))

    specs = MDL.param_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    out = []
    for path, leaf in flat:
        stacked = "layers" in jax.tree_util.keystr(path)
        out.append(leaf_spec(leaf.shape, stacked))
    return jax.tree_util.tree_unflatten(treedef, out)


def seq_parallel_hooks(mesh: Mesh):
    """(residual_cs, kv_cs): residual stream sharded (batch→data,
    seq→model); K/V replicated over model for full-context attention
    (GSPMD inserts the per-layer KV all-gather)."""
    da = data_axes(mesh)

    def residual_cs(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(da, "model", None)))

    def kv_cs(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(da, None, None, None)))

    return residual_cs, kv_cs
