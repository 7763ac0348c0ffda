"""Model assembly for all assigned architecture families.

One generic decoder/encoder stack, specialised by `ModelConfig.family`:
  dense/moe/vlm : pre-norm attention + (MLP | MoE) residual blocks
  ssm           : mamba-1 mixer blocks (attention-free)
  hybrid        : parallel attention ∥ mamba heads + MLP (hymba)
  encoder       : bidirectional pre-LN transformer (hubert)

All layers are stacked on a leading axis and executed with `jax.lax.scan`
(+ optional `jax.checkpoint`), keeping the HLO size O(1) in depth — both a
compile-time necessity on this box and the production pattern for 1000+
node runs.

Three modes:
  train   — full-sequence forward, no cache, returns token logits
  prefill — full-sequence forward, emits a decode cache
  decode  — single-token step against a (ring-buffered) KV / SSM cache
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models.config import (DENSE, ENCODER, HYBRID, MOE as MOE_F, SSM,
                                 VLM, ModelConfig)

PyTree = Any


def _dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# =============================== parameters ===================================
def _layer_shapes(cfg: ModelConfig, dense: bool = False
                  ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Per-layer leaf name → (shape-without-L, dtype). ``dense``: one of the
    leading layers whose MLP is dense (``first_dense_layers``)."""
    m, pd = cfg.d_model, _dt(cfg.param_dtype)
    h, kv, hd = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim
    out: Dict[str, Tuple[Tuple[int, ...], Any]] = {}

    def norm(prefix: str):
        if cfg.norm_type == "rmsnorm":
            out[f"{prefix}.scale"] = ((m,), pd)
        elif cfg.norm_type == "layernorm":
            out[f"{prefix}.scale"] = ((m,), pd)
            out[f"{prefix}.bias"] = ((m,), pd)
        # nonparam_ln: no params

    if cfg.has_mla:
        norm("ln_attn")
        r = cfg.kv_lora_rank
        out["attn.wq"] = ((m, h, hd), pd)
        out["attn.wkv_a"] = ((m, cfg.latent_dim), pd)   # [latent, rope key]
        out["attn.kv_norm.scale"] = ((r,), pd)
        out["attn.wkv_b"] = ((r, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
                             pd)                        # [k_nope, v]
        out["attn.wo"] = ((h, cfg.v_head_dim, m), pd)
    elif cfg.has_attention:
        norm("ln_attn")
        # 3D layout keeps head vs head_dim sharding choices expressible
        out["attn.wq"] = ((m, h, hd), pd)
        out["attn.wk"] = ((m, kv, hd), pd)
        out["attn.wv"] = ((m, kv, hd), pd)
        out["attn.wo"] = ((h, hd, m), pd)
        if cfg.qkv_bias:
            out["attn.bq"] = ((h, hd), pd)
            out["attn.bk"] = ((kv, hd), pd)
            out["attn.bv"] = ((kv, hd), pd)
    if cfg.has_ssm:
        if not cfg.has_attention:
            norm("ln_ssm")
        di, n, r, k = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_eff, cfg.ssm_conv
        out["ssm.in_x"] = ((m, di), pd)      # split leaves: never slice a
        out["ssm.in_z"] = ((m, di), pd)      # model-sharded dim
        out["ssm.conv_w"] = ((k, di), pd)
        out["ssm.conv_b"] = ((di,), pd)
        out["ssm.x_proj"] = ((di, r + 2 * n), pd)
        out["ssm.dt_proj"] = ((r, di), pd)
        out["ssm.dt_bias"] = ((di,), pd)
        out["ssm.A_log"] = ((di, n), jnp.float32)
        out["ssm.D"] = ((di,), jnp.float32)
        out["ssm.out_proj"] = ((di, m), pd)
    if cfg.has_mlp or dense:
        norm("ln_mlp")
        f = cfg.dense_d_ff if dense else cfg.d_ff
        if cfg.mlp_act == "silu":
            out["mlp.w_gate"] = ((m, f), pd)
            out["mlp.w_up"] = ((m, f), pd)
            out["mlp.w_down"] = ((f, m), pd)
        else:
            out["mlp.w_in"] = ((m, f), pd)
            out["mlp.b_in"] = ((f,), pd)
            out["mlp.w_out"] = ((f, m), pd)
            out["mlp.b_out"] = ((m,), pd)
    elif cfg.has_moe:
        norm("ln_mlp")
        e, f = cfg.num_experts, cfg.d_ff
        out["moe.router"] = ((m, e), pd)
        out["moe.w_gate"] = ((e, m, f), pd)
        out["moe.w_up"] = ((e, m, f), pd)
        out["moe.w_down"] = ((e, f, m), pd)
        if cfg.num_shared_experts:
            fs = cfg.num_shared_experts * f
            out["moe.shared_gate"] = ((m, fs), pd)
            out["moe.shared_up"] = ((m, fs), pd)
            out["moe.shared_down"] = ((fs, m), pd)
    return out


# Leaves the forward reads only as `.astype(compute_dtype)`. Every other leaf
# is read in float32 (norm scales and biases, moe.router, ssm.dt_bias,
# ssm.A_log, ssm.D) and keeps its dtype.
_COMPUTE_DTYPE_LEAVES = frozenset({
    "embed", "lm_head",
    "attn.wq", "attn.wk", "attn.wv", "attn.wo", "attn.bq", "attn.bk",
    "attn.bv", "attn.wkv_a", "attn.wkv_b",
    "mlp.w_gate", "mlp.w_up", "mlp.w_down",
    "mlp.w_in", "mlp.b_in", "mlp.w_out", "mlp.b_out",
    "moe.w_gate", "moe.w_up", "moe.w_down",
    "moe.shared_gate", "moe.shared_up", "moe.shared_down",
    "ssm.in_x", "ssm.in_z", "ssm.conv_w", "ssm.conv_b", "ssm.x_proj",
    "ssm.dt_proj", "ssm.out_proj",
})


def serving_params(cfg: ModelConfig, params: PyTree) -> PyTree:
    """The tree the serving step programs take: every leaf the forward reads
    only as `.astype(compute_dtype)` stored in the compute dtype, so the
    cast is made once here and not on every launch. The matmuls see the
    same values. Maps arrays to arrays and ShapeDtypeStructs to
    ShapeDtypeStructs; returns `params` itself when the two dtypes agree."""
    cd = _dt(cfg.compute_dtype)
    if _dt(cfg.param_dtype) == cd:
        return params

    def cast(path, x):
        if path[-1].key not in _COMPUTE_DTYPE_LEAVES or x.dtype == cd:
            return x
        if isinstance(x, jax.ShapeDtypeStruct):
            return x.update(dtype=cd)
        return x.astype(cd)

    return jax.tree_util.tree_map_with_path(cast, params)


def tree_bytes(tree: PyTree) -> int:
    """Device bytes of a tree of arrays or ShapeDtypeStructs."""
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def param_specs(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStructs for the full parameter tree (stacked layers)."""
    m, vp, pd = cfg.d_model, cfg.padded_vocab, _dt(cfg.param_dtype)
    fd = cfg.first_dense_layers
    tree: Dict[str, Any] = {"layers": {}}
    for name, (shape, dt) in _layer_shapes(cfg).items():
        tree["layers"][name] = jax.ShapeDtypeStruct(
            (cfg.num_layers - fd,) + shape, dt)
    if fd:
        tree["dense_layers"] = {
            name: jax.ShapeDtypeStruct((fd,) + shape, dt)
            for name, (shape, dt) in _layer_shapes(cfg, dense=True).items()}
    if cfg.family != ENCODER:
        tree["embed"] = jax.ShapeDtypeStruct((vp, m), pd)
    if not cfg.tie_embeddings:
        tree["lm_head"] = jax.ShapeDtypeStruct((m, vp), pd)
    if cfg.norm_type == "rmsnorm":
        tree["final_norm.scale"] = jax.ShapeDtypeStruct((m,), pd)
    elif cfg.norm_type == "layernorm":
        tree["final_norm.scale"] = jax.ShapeDtypeStruct((m,), pd)
        tree["final_norm.bias"] = jax.ShapeDtypeStruct((m,), pd)
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_layers(key, shape, std: float, dtype):
    """A stacked leaf drawn one layer at a time, layer i from split i of
    `key`, in one program: a single layer's float32 draw is live at a time
    (drawn eagerly, every layer's would be queued at once)."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], jnp.float32) * std
                   ).astype(dtype),
        jax.random.split(key, shape[0]))


def init_params(cfg: ModelConfig, key: jax.Array,
                specs: Optional[PyTree] = None) -> PyTree:
    """Seeded weights. Each leaf is drawn in float32 from its own key and
    cast to its spec's dtype; `specs` (default `param_specs(cfg)`) may be
    its serving tree, so that no float32 copy of a compute-dtype leaf
    outlives its own draw.  A stacked `moe.*` leaf is drawn one layer at a
    time, each layer from its own split of the leaf's key, so that no
    float32 copy of all its layers' experts is ever held."""
    specs = param_specs(cfg) if specs is None else specs
    flat_paths, treedef = jax.tree_util.tree_flatten_with_path(specs)
    keys = jax.random.split(key, len(flat_paths))
    vals = []
    for k, (path, s) in zip(keys, flat_paths):
        p = jax.tree_util.keystr(path)
        stacked = "layers" in p
        core_ndim = len(s.shape) - (1 if stacked else 0)
        if "A_log" in p:
            n = s.shape[-1]
            v = jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                                 s.shape)
        elif "ssm.D" in p:
            v = jnp.ones(s.shape, jnp.float32)
        elif core_ndim == 1:
            v = (jnp.ones if "scale" in p else jnp.zeros)(s.shape, jnp.float32)
        else:
            if "attn.w" in p:
                start = 1 if stacked else 0
                fan_in = (s.shape[start] if p.endswith(
                    ("wq']", "wk']", "wv']", "wkv_a']", "wkv_b']"))
                          else s.shape[start] * s.shape[start + 1])
            else:
                fan_in = s.shape[-2]
            std = 1.0 / math.sqrt(max(1, fan_in))
            if stacked and "moe." in p:
                v = _draw_layers(k, s.shape, std, s.dtype)
            else:
                v = jax.random.normal(k, s.shape, jnp.float32) * std
        vals.append(v.astype(s.dtype))
    return treedef.unflatten(vals)


def param_count_actual(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ================================ cache =======================================
def cache_specs(cfg: ModelConfig, batch: int, cache_len: int,
                include_row_idx: bool = False) -> Dict[str, Any]:
    """ShapeDtypeStructs for the decode cache. include_row_idx adds the
    per-row write cursor (continuous batching / sharded-length caches —
    the write becomes a masked elementwise update instead of a DUS on a
    sharded dim)."""
    ln, cd = cfg.num_layers, _dt(cfg.compute_dtype)
    out: Dict[str, Any] = {"idx": jax.ShapeDtypeStruct((), jnp.int32)}
    if include_row_idx:
        out["row_idx"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    if cfg.has_attention:
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        lc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        out["k"] = jax.ShapeDtypeStruct((ln, batch, lc, kv, hd), cd)
        out["v"] = jax.ShapeDtypeStruct((ln, batch, lc, kv, hd), cd)
        out["slot_pos"] = jax.ShapeDtypeStruct((batch, lc), jnp.int32)
    if cfg.has_ssm:
        out["conv"] = jax.ShapeDtypeStruct(
            (ln, batch, cfg.ssm_conv - 1, cfg.d_inner), jnp.float32)
        out["h"] = jax.ShapeDtypeStruct(
            (ln, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)
    return out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               include_row_idx: bool = False) -> Dict[str, Any]:
    specs = cache_specs(cfg, batch, cache_len, include_row_idx)
    out = {k: jnp.zeros(s.shape, s.dtype) for k, s in specs.items()}
    if "slot_pos" in out:
        out["slot_pos"] = jnp.full(specs["slot_pos"].shape, -1, jnp.int32)
    return out


def padded_head_dim(head_dim: int) -> int:
    """Pool lane width: head_dim zero-padded up to the TPU register lane
    count so the Pallas kernel's (KV·P, ps, 128) view is a free reshape."""
    return -(-head_dim // 128) * 128


def paged_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int,
                      batch: int, quant: bool = False) -> Dict[str, Any]:
    """Paged KV layout: one GLOBAL pool of fixed-size pages per layer
    instead of per-row dense caches.  Sequences address the pool through a
    per-row block table (passed separately, host-managed), so a shared
    instruction prefix is one set of pages referenced by every row.  SSM
    conv/h state stays per-row dense — it is O(1) in sequence length.

    Pools are stored pre-folded as (layers, KV, P, ps, Dp) with head_dim
    zero-padded to Dp = 128 lanes: the per-layer (KV, P, ps, Dp) slice
    reshapes to the Pallas kernel's (KV·P, ps, Dp) view for free, so the
    decode step pays no per-step transpose.  With `quant`, int8 shadow
    pools plus per-(layer, kv-head, page) scales are added for
    quantize-on-commit of frozen shared-prefix pages.

    Latent attention keeps one pool "ckv" of (layers, 1, P, ps, Dp): each
    token's normalised latent and its rotary key, kv_lora_rank +
    qk_rope_head_dim values padded to Dp lanes, and no V pool."""
    ln, cd = cfg.num_layers, _dt(cfg.compute_dtype)
    out: Dict[str, Any] = {"idx": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.has_mla:
        assert not quant, "latent pools are not quantized"
        out["ckv"] = jax.ShapeDtypeStruct(
            (ln, 1, num_pages, page_size, padded_head_dim(cfg.latent_dim)), cd)
    elif cfg.has_attention:
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        dp = padded_head_dim(hd)
        out["k"] = jax.ShapeDtypeStruct((ln, kv, num_pages, page_size, dp), cd)
        out["v"] = jax.ShapeDtypeStruct((ln, kv, num_pages, page_size, dp), cd)
        if quant:
            out["kq"] = jax.ShapeDtypeStruct(
                (ln, kv, num_pages, page_size, dp), jnp.int8)
            out["vq"] = jax.ShapeDtypeStruct(
                (ln, kv, num_pages, page_size, dp), jnp.int8)
            out["kscale"] = jax.ShapeDtypeStruct((ln, kv, num_pages),
                                                 jnp.float32)
            out["vscale"] = jax.ShapeDtypeStruct((ln, kv, num_pages),
                                                 jnp.float32)
    if cfg.has_ssm:
        out["conv"] = jax.ShapeDtypeStruct(
            (ln, batch, cfg.ssm_conv - 1, cfg.d_inner), jnp.float32)
        out["h"] = jax.ShapeDtypeStruct(
            (ln, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)
    return out


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     batch: int = 0, quant: bool = False) -> Dict[str, Any]:
    specs = paged_cache_specs(cfg, num_pages, page_size, batch, quant)
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in specs.items()}


# ================================ blocks ======================================
def _norm_p(lp: Dict[str, jax.Array], prefix: str) -> Optional[dict]:
    scale = lp.get(f"{prefix}.scale")
    bias = lp.get(f"{prefix}.bias")
    if scale is None and bias is None:
        return None
    return {"scale": scale, "bias": bias}


def _fold_write(x: jax.Array, dp: int) -> jax.Array:
    """(..., KV, D) → (KV, ..., Dp): move the kv-head axis to the front and
    zero-pad head_dim to the pool's padded lane width."""
    x = jnp.moveaxis(x, -2, 0)
    pad = dp - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


def _page_slots(bt: jax.Array, positions: jax.Array, num_pages: int,
                page_size: int):
    """(page, offset) of each position through the block table bt (B, NB);
    positions (B,) or (B, S).  A slot with no page (pad position, entry -1
    or past the table, i.e. past max_len) gets page `num_pages`, out of
    bounds, so that its write is dropped: the sequence keeps decoding
    against a frozen cache.  The dense layout ring-wraps instead; both are
    out of contract past max_len."""
    nb = bt.shape[1]
    blk = jnp.clip(positions, 0, None) // page_size
    pos2 = blk if blk.ndim == 2 else blk[:, None]
    entry = jnp.take_along_axis(bt, jnp.clip(pos2, 0, nb - 1), axis=1)
    entry = entry.reshape(blk.shape)
    ok = (positions >= 0) & (blk < nb) & (entry >= 0)
    return (jnp.where(ok, entry, num_pages),
            jnp.clip(positions, 0, None) % page_size)


def _dequant_pages(qd: Dict[str, jax.Array], safe_pages: jax.Array,
                   kp: jax.Array, vp: jax.Array):
    """Replace frozen (quantized) pages of a gathered fp view with their
    dequantized int8 shadow.  safe_pages (npre,) clipped page ids;
    kp/vp (KV, npre, ps, Dp) gathered fp pages."""
    fl = qd["flags"][safe_pages] > 0                       # (npre,)
    kdq = (qd["kq"][:, safe_pages].astype(jnp.float32)
           * qd["kscale"][:, safe_pages][..., None, None]).astype(kp.dtype)
    vdq = (qd["vq"][:, safe_pages].astype(jnp.float32)
           * qd["vscale"][:, safe_pages][..., None, None]).astype(vp.dtype)
    kp = jnp.where(fl[None, :, None, None], kdq, kp)
    vp = jnp.where(fl[None, :, None, None], vdq, vp)
    return kp, vp


def _attention(cfg: ModelConfig, x, lp, positions, mode, ck, cv, slot_pos, idx,
               attn_fn=None, decode_attn_fn=None, extend_offset: int = 0,
               row_idx=None, kv_cs=MOE.Identity, paged=None):
    """x (B,S,M). Returns (out (B,S,M), new_ck, new_cv).
    extend_offset > 0 (prefill mode): attend over [cache[:offset] ++ new] and
    write the new K/V at slot offset — chunked prefill / shared-prefix reuse.
    paged (dict or None): block-table addressed page-pool layout — ck/cv are
    then pre-folded (KV, P, ps, Dp) pools (Dp = head_dim padded to 128),
    paged["block_tables"] is (B, NB) page ids
    (-1 = invalid; invalid/out-of-range writes are dropped), prefill may
    carry paged["prefix_table"]/["prefix_len"] pointing at shared prefix
    pages that are read in place, never replicated per row, and
    paged["quant"] (if set) holds int8 shadow pools + per-page scales +
    frozen flags for dequantizing committed shared pages on read."""
    B, S, m = x.shape
    h, kv, hd = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim
    cd = _dt(cfg.compute_dtype)
    q = jnp.einsum("bsm,mhd->bshd", x, lp["attn.wq"].astype(cd))
    k = jnp.einsum("bsm,mhd->bshd", x, lp["attn.wk"].astype(cd))
    v = jnp.einsum("bsm,mhd->bshd", x, lp["attn.wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + lp["attn.bq"].astype(cd)
        k = k + lp["attn.bk"].astype(cd)
        v = v + lp["attn.bv"].astype(cd)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    if mode != "decode":
        k = kv_cs(k)        # sequence-parallel attention: kv replicated
        v = kv_cs(v)

    new_ck, new_cv = ck, cv
    if paged is not None and mode == "decode":
        bt = paged["block_tables"]
        KV_, P_, ps_, Dp_ = ck.shape
        pos = positions[:, 0]                                     # (B,)
        page, off = _page_slots(bt, pos, P_, ps_)
        # per-axis indexing keeps the P_ out-of-bounds drop trick safe: the
        # page axis is indexed on its own, so an invalid id can never fold
        # into a neighbouring kv-head's page 0
        new_ck = ck.at[:, page, off].set(
            _fold_write(k[:, 0], Dp_).astype(ck.dtype), mode="drop")
        new_cv = cv.at[:, page, off].set(
            _fold_write(v[:, 0], Dp_).astype(cv.dtype), mode="drop")
        fn = decode_attn_fn or L.decode_attention_paged
        o = fn(q[:, 0], new_ck, new_cv, bt, pos, head_dim=hd,
               quant=paged.get("quant"))[:, None]
    elif paged is not None:
        # paged prefill: suffix flash vs its own KV merged with a broadcast
        # (never replicated) read of the shared prefix pages; new KV is
        # committed straight into the rows' pages
        assert mode == "prefill" and not cfg.sliding_window
        bt = paged["block_tables"]
        pt = paged.get("prefix_table")
        plen = paged.get("prefix_len", jnp.int32(0))
        KV_, P_, ps_, Dp_ = ck.shape
        if pt is not None and pt.shape[0]:
            safe_pt = jnp.clip(pt, 0, P_ - 1)
            kp = ck[:, safe_pt]                       # (KV, npre, ps, Dp)
            vp = cv[:, safe_pt]
            if paged.get("quant") is not None:
                kp, vp = _dequant_pages(paged["quant"], safe_pt, kp, vp)
            kp = kp.transpose(1, 2, 0, 3).reshape(-1, KV_, Dp_)[..., :hd]
            vp = vp.transpose(1, 2, 0, 3).reshape(-1, KV_, Dp_)[..., :hd]
        else:
            kp = jnp.zeros((0, KV_, hd), ck.dtype)
            vp = jnp.zeros((0, KV_, hd), cv.dtype)
        o = L.prefix_suffix_attention(q, kp, vp, k, v, positions, plen)
        page, off = _page_slots(bt, positions, P_, ps_)
        new_ck = ck.at[:, page, off].set(
            _fold_write(k, Dp_).astype(ck.dtype), mode="drop")
        new_cv = cv.at[:, page, off].set(
            _fold_write(v, Dp_).astype(cv.dtype), mode="drop")
    elif mode == "decode":
        lc = ck.shape[1]
        if row_idx is not None:
            # per-row write slots (continuous batching: ragged fill levels)
            slot_b = row_idx % lc                          # (B,)
            hit = (jnp.arange(lc)[None, :] == slot_b[:, None])  # (B, lc)
            new_ck = jnp.where(hit[:, :, None, None], k.astype(ck.dtype), ck)
            new_cv = jnp.where(hit[:, :, None, None], v.astype(cv.dtype), cv)
            spos = jnp.where(hit, positions[:, :1], slot_pos)
        else:
            slot = idx % lc
            new_ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
            new_cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
            spos = jnp.where(jnp.arange(lc)[None, :] == slot, positions[:, :1],
                             slot_pos)
        fn = decode_attn_fn or L.decode_attention
        o = fn(q[:, 0], new_ck, new_cv, spos, positions[:, 0])[:, None]
    elif mode == "prefill" and extend_offset > 0:
        off = extend_offset
        lc = ck.shape[1]
        assert off + S <= lc and not cfg.sliding_window, (off, S, lc)
        k_all = jnp.concatenate([ck[:, :off].astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([cv[:, :off].astype(v.dtype), v], axis=1)
        kv_pos = jnp.concatenate([slot_pos[:, :off], positions], axis=1)
        fn = attn_fn or L.flash_attention
        o = fn(q, k_all, v_all, positions, kv_pos,
               causal=cfg.causal, window=0,
               prefix_len=cfg.num_prefix_tokens if cfg.family == VLM else 0)
        new_ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, off, 0, 0))
        new_cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, off, 0, 0))
    else:
        fn = attn_fn or L.flash_attention
        o = fn(q, k, v, positions, positions,
               causal=cfg.causal, window=cfg.sliding_window,
               prefix_len=cfg.num_prefix_tokens if cfg.family == VLM else 0)
        if mode == "prefill":
            lc = ck.shape[1]
            if S >= lc:
                shift = S % lc
                new_ck = jnp.roll(k[:, S - lc:].astype(ck.dtype), shift, axis=1)
                new_cv = jnp.roll(v[:, S - lc:].astype(cv.dtype), shift, axis=1)
            else:
                new_ck = jax.lax.dynamic_update_slice(
                    ck, k.astype(ck.dtype), (0, 0, 0, 0))
                new_cv = jax.lax.dynamic_update_slice(
                    cv, v.astype(cv.dtype), (0, 0, 0, 0))
    out = jnp.einsum("bshd,hdm->bsm", o, lp["attn.wo"].astype(cd))
    return out, new_ck, new_cv


def _rope_setup(cfg: ModelConfig):
    """(inverse frequencies, factor on the rotated values, softmax scale) of
    latent attention's rotary part: YaRN-scaled when `yarn_factor` is set
    (DeepSeek-V2's `DeepseekV2YarnRotaryEmbedding`)."""
    dr = cfg.qk_rope_head_dim
    scale = cfg.head_dim ** -0.5
    if not cfg.yarn_factor:
        return L.rope_inv_freq(dr, cfg.rope_theta), 1.0, scale
    f = cfg.yarn_factor
    inv = L.rope_inv_freq(dr, cfg.rope_theta, (
        f, cfg.yarn_original_max_pos, cfg.yarn_beta_fast, cfg.yarn_beta_slow))
    ms_all = L.yarn_mscale(f, cfg.yarn_mscale_all_dim) \
        if cfg.yarn_mscale_all_dim else 1.0
    return inv, L.yarn_mscale(f, cfg.yarn_mscale) / ms_all, scale * ms_all ** 2


def _pair_rope(x, positions, inv_freq, factor: float):
    """Rotary embedding of adjacent pairs (2i, 2i+1) at frequency i, as
    DeepSeek-V2 does: the pairs are de-interleaved (evens, then odds) and
    the halves rotated.  The result stays de-interleaved; queries and keys
    get the same permutation, so their dot products are those of the
    pairwise rotation."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    y = L.rope(x, positions, 0.0, inv_freq)
    return y if factor == 1.0 else (y * factor).astype(y.dtype)


def _mla_attention(cfg: ModelConfig, x, lp, positions, mode, pool, layer,
                   paged):
    """Latent attention (DeepSeek-V2, arXiv:2405.04434). x (B,S,M).  The
    cache is the whole latent pool (layers, 1, P, ps, Dp), read and written
    at `layer`.  Decode scores the absorbed query (q_nope through W_kvb's
    key half) against the cached [latent, rope key] and maps the latent
    output through W_kvb's value half; prefill and train up-project the
    latents to per-head keys and values.  Returns (out (B,S,M), pool)."""
    cd = _dt(cfg.compute_dtype)
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dl = cfg.qk_nope_head_dim, cfg.latent_dim
    inv, factor, scale = _rope_setup(cfg)
    wkv_b = lp["attn.wkv_b"].astype(cd)                  # (r, h, dn + dv)
    with jax.named_scope("mla"):
        q = jnp.einsum("bsm,mhd->bshd", x, lp["attn.wq"].astype(cd))
        q_pe = _pair_rope(q[..., dn:], positions, inv, factor)
        kva = jnp.einsum("bsm,mr->bsr", x, lp["attn.wkv_a"].astype(cd))
        c = L.rmsnorm(kva[..., :r], lp["attn.kv_norm.scale"], cfg.eps)
        k_pe = _pair_rope(kva[:, :, None, r:], positions, inv, factor)
        lat = jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)   # (B, S, dl)

        def write(pool, page, off, vals):
            vals = jnp.pad(vals, [(0, 0)] * (vals.ndim - 1)
                           + [(0, pool.shape[-1] - dl)])
            return pool.at[layer, 0, page, off].set(vals.astype(pool.dtype),
                                                    mode="drop")

        if mode == "decode":
            bt = paged["block_tables"]
            P_, ps_ = pool.shape[2], pool.shape[3]
            pos = positions[:, 0]
            pool = write(pool, *_page_slots(bt, pos, P_, ps_), lat[:, 0])
            q_lat = jnp.einsum("bhd,rhd->bhr", q[:, 0, :, :dn],
                               wkv_b[..., :dn],
                               preferred_element_type=jnp.float32)
            qf = jnp.concatenate([q_lat, q_pe[:, 0].astype(jnp.float32)],
                                 axis=-1)                       # (B, h, dl)
            B, NB = bt.shape
            pages = pool[layer, 0, jnp.clip(bt, 0, P_ - 1)]  # (B,NB,ps,Dp)
            pages = pages.reshape(B, NB * ps_, 1, pool.shape[-1])
            slot = jnp.broadcast_to(jnp.arange(NB * ps_, dtype=jnp.int32),
                                    (B, NB * ps_))
            slot = jnp.where(jnp.repeat(bt >= 0, ps_, axis=1), slot, -1)
            o_lat = L.decode_attention(qf, pages[..., :dl], pages[..., :r],
                                       slot, pos, scale=scale)
            o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(cd),
                           wkv_b[..., dn:])[:, None]
        else:
            def expand(c, k_pe):
                kv = jnp.einsum("...r,rhd->...hd", c, wkv_b)
                k_pe = jnp.broadcast_to(k_pe[..., None, :],
                                        kv.shape[:-1] + k_pe.shape[-1:])
                return (jnp.concatenate([kv[..., :dn], k_pe], axis=-1),
                        kv[..., dn:])

            k, v = expand(c, k_pe[:, :, 0])
            qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
            pt = None if paged is None else paged.get("prefix_table")
            plen = jnp.int32(0) if paged is None else \
                paged.get("prefix_len", jnp.int32(0))
            if pt is not None and pt.shape[0]:
                # shared prefix pages read in place, one copy for the batch
                P_ = pool.shape[2]
                pre = pool[layer, 0, jnp.clip(pt, 0, P_ - 1)]
                pre = pre.reshape(-1, pool.shape[-1])
                kp, vp = expand(pre[:, :r], pre[:, r:dl])
            else:
                kp = jnp.zeros((0,) + k.shape[2:], k.dtype)
                vp = jnp.zeros((0,) + v.shape[2:], v.dtype)
            o = L.prefix_suffix_attention(qf, kp, vp, k, v, positions, plen,
                                          scale=scale)
            if paged is not None:
                bt = paged["block_tables"]
                pool = write(pool, *_page_slots(bt, positions, pool.shape[2],
                                                pool.shape[3]), lat)
        out = jnp.einsum("bshd,hdm->bsm", o, lp["attn.wo"].astype(cd))
    return out, pool


def _mlp(cfg: ModelConfig, x, lp, valid, dense: bool):
    """The block's MLP on the normed x (B,S,M): dense SwiGLU / GELU, or the
    experts.  Returns (y, distinct experts the valid tokens chose, or
    None)."""
    B, S, m = x.shape
    if cfg.has_moe and not dense:
        moe_p = {k[4:]: v for k, v in lp.items() if k.startswith("moe.")}
        y, idx = MOE.moe_block(x.reshape(B * S, m), moe_p,
                               num_experts=cfg.num_experts, top_k=cfg.top_k,
                               norm_topk_prob=cfg.norm_topk_prob,
                               compute_dtype=_dt(cfg.compute_dtype))
        return y.reshape(B, S, m), MOE.experts_touched(
            idx, cfg.num_experts, valid.reshape(-1))
    if cfg.mlp_act == "silu":
        return L.swiglu_mlp(x, lp["mlp.w_gate"].astype(x.dtype),
                            lp["mlp.w_up"].astype(x.dtype),
                            lp["mlp.w_down"].astype(x.dtype)), None
    return L.gelu_mlp(x, lp["mlp.w_in"].astype(x.dtype),
                      lp["mlp.b_in"].astype(x.dtype),
                      lp["mlp.w_out"].astype(x.dtype),
                      lp["mlp.b_out"].astype(x.dtype)), None


def _block(cfg: ModelConfig, x, lp, positions, mode, cache_l, *,
           attn_fn=None, decode_attn_fn=None, scan_fn=None,
           extend_offset: int = 0, kv_cs=MOE.Identity, paged=None):
    """One residual block. cache_l: per-layer cache slice dict (or {}).
    Returns (x, new cache slice, distinct experts chosen or None)."""
    eps = cfg.eps
    new_cache = dict(cache_l)
    slot_pos = cache_l.get("slot_pos")
    idx = cache_l.get("idx", jnp.int32(0))
    if paged is not None and "kq" in cache_l:
        # attach this layer's int8 shadow pool + scales (scanned-in slices)
        # alongside the shared frozen-page flags
        paged = {**paged, "quant": {
            "kq": cache_l["kq"], "vq": cache_l["vq"],
            "kscale": cache_l["kscale"], "vscale": cache_l["vscale"],
            "flags": paged["quant_flags"]}}

    if cfg.family == HYBRID:
        xin = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_attn"), eps)
        a, nk, nv = _attention(cfg, xin, lp, positions, mode,
                               cache_l.get("k"), cache_l.get("v"), slot_pos, idx,
                               attn_fn, decode_attn_fn, extend_offset,
                               cache_l.get("row_idx"), kv_cs, paged)
        state = None
        if mode != "train":
            state = M.SSMState(conv=cache_l["conv"], h=cache_l["h"])
        s, new_state = M.mamba_mixer(
            xin, {k[4:]: v for k, v in lp.items() if k.startswith("ssm.")},
            ssm_state_dim=cfg.ssm_state, dt_rank=cfg.dt_rank_eff,
            conv_dim=cfg.ssm_conv, mode=("decode" if mode == "decode" else "train"),
            state=state, scan_fn=scan_fn or M.selective_scan)
        x = x + 0.5 * (a + s)
        if mode != "train":
            new_cache.update(k=nk, v=nv, conv=new_state.conv, h=new_state.h)
        xin2 = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_mlp"), eps)
        x = x + L.swiglu_mlp(xin2, lp["mlp.w_gate"].astype(x.dtype),
                             lp["mlp.w_up"].astype(x.dtype),
                             lp["mlp.w_down"].astype(x.dtype))
        return x, new_cache, None

    if cfg.family == SSM:
        xin = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_ssm"), eps)
        state = None
        if mode != "train":
            state = M.SSMState(conv=cache_l["conv"], h=cache_l["h"])
        s, new_state = M.mamba_mixer(
            xin, {k[4:]: v for k, v in lp.items() if k.startswith("ssm.")},
            ssm_state_dim=cfg.ssm_state, dt_rank=cfg.dt_rank_eff,
            conv_dim=cfg.ssm_conv, mode=("decode" if mode == "decode" else "train"),
            state=state, scan_fn=scan_fn or M.selective_scan)
        if mode != "train":
            new_cache.update(conv=new_state.conv, h=new_state.h)
        return x + s, new_cache, None

    # attention families: dense / moe / encoder / vlm
    xin = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_attn"), eps)
    a, nk, nv = _attention(cfg, xin, lp, positions, mode,
                           cache_l.get("k"), cache_l.get("v"), slot_pos, idx,
                           attn_fn, decode_attn_fn, extend_offset,
                           cache_l.get("row_idx"), kv_cs, paged)
    x = x + a
    if mode != "train" and cfg.has_attention:
        new_cache.update(k=nk, v=nv)
    xin2 = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_mlp"), eps)
    y, touched = _mlp(cfg, xin2, lp, positions >= 0, dense=False)
    return x + y, new_cache, touched


def _mla_stacks(cfg: ModelConfig, params, x, positions, mode, pool, paged):
    """The layers of a latent-attention model: the leading dense layers,
    then the expert layers, each a scan over its own stacked weights.  The
    latent pool (stacked over all layers) rides in the scans' carry and
    each layer reads and writes its own slice in place.  Returns (x, pool,
    distinct experts chosen per expert layer)."""
    def run(stack, x, pool, first: int, dense: bool):
        n = jax.tree.leaves(stack)[0].shape[0]

        def body(carry, xs):
            x, pool = carry
            lp, layer = xs
            xin = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_attn"),
                               cfg.eps)
            a, pool = _mla_attention(cfg, xin, lp, positions, mode, pool,
                                     layer, paged)
            x = x + a
            xin = L.apply_norm(cfg.norm_type, x, _norm_p(lp, "ln_mlp"),
                               cfg.eps)
            y, touched = _mlp(cfg, xin, lp, positions >= 0, dense)
            return (x + y, pool), touched

        (x, pool), touched = jax.lax.scan(
            body, (x, pool), (stack, first + jnp.arange(n, dtype=jnp.int32)))
        return x, pool, touched

    fd = cfg.first_dense_layers
    if fd:
        x, pool, _ = run(params["dense_layers"], x, pool, 0, True)
    return run(params["layers"], x, pool, fd, False)


# ============================== full forward ==================================
_LAYER_CACHE_KEYS = ("k", "v", "kq", "vq", "kscale", "vscale", "conv", "h")


def forward(cfg: ModelConfig, params: PyTree, batch: Dict[str, jax.Array],
            mode: str = "train", cache: Optional[Dict[str, Any]] = None, *,
            remat: bool = True, attn_fn=None, decode_attn_fn=None,
            scan_fn=None, logits_cs=MOE.Identity, last_only: bool = False,
            unroll_layers: bool = False, extend_offset: int = 0,
            residual_cs=MOE.Identity, kv_cs=MOE.Identity,
            remat_policy: str = "nothing"
            ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """Run the stack. batch: tokens (B,S) int32 | embeds (B,S,M); positions
    (B,S). Returns (logits (B,S,V), new_cache or None).  For a model with
    experts, a returned paged cache also holds "expert_loads": per expert
    layer, the distinct experts the step's tokens (pads excluded) routed
    to."""
    cd = _dt(cfg.compute_dtype)
    positions = batch["positions"]

    if "embeds" in batch:                       # encoder / stub frontend
        x = batch["embeds"].astype(cd)
    else:
        x = jnp.take(params["embed"].astype(cd), batch["tokens"], axis=0)
        if cfg.scale_embed:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
        if cfg.family == VLM and "prefix_embeds" in batch:
            x = jnp.concatenate([batch["prefix_embeds"].astype(cd), x], axis=1)
            positions = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(batch["prefix_embeds"].shape[1],
                                             dtype=jnp.int32)[None],
                                  batch["prefix_embeds"].shape[:2]),
                 batch["positions"] + batch["prefix_embeds"].shape[1]], axis=1)

    stacked = params["layers"]
    shared_cache = {}
    layer_cache = {}
    if cache is not None:
        layer_cache = {k: cache[k] for k in _LAYER_CACHE_KEYS if k in cache}
        shared_cache = {k: v for k, v in cache.items()
                        if k not in _LAYER_CACHE_KEYS}

    idx = shared_cache.get("idx", jnp.int32(0))
    slot_pos = shared_cache.get("slot_pos")
    row_idx = shared_cache.get("row_idx")
    paged = None
    if "block_tables" in shared_cache:
        paged = {"block_tables": shared_cache["block_tables"]}
        if "prefix_table" in shared_cache:
            paged["prefix_table"] = shared_cache["prefix_table"]
            paged["prefix_len"] = shared_cache.get("prefix_len", jnp.int32(0))
        if "quant_flags" in shared_cache:
            paged["quant_flags"] = shared_cache["quant_flags"]

    x = residual_cs(x)

    if cfg.has_mla:
        assert cache is None or paged is not None, \
            "latent attention is served from the paged layout"
        pool = None if cache is None else cache["ckv"]
        x, pool, touched = _mla_stacks(cfg, params, x, positions, mode, pool,
                                       paged)
        new_layer_cache = {} if pool is None else {"ckv": pool}
    else:
        assert not cfg.first_dense_layers, \
            "leading dense layers come with latent attention only"

        def body(x, xs):
            lp, cl = xs
            cl = dict(cl)
            if slot_pos is not None:
                cl["slot_pos"] = slot_pos
            if row_idx is not None:
                cl["row_idx"] = row_idx
            cl["idx"] = idx
            y, nc, touched = _block(cfg, x, lp, positions, mode, cl,
                                    attn_fn=attn_fn,
                                    decode_attn_fn=decode_attn_fn,
                                    scan_fn=scan_fn,
                                    extend_offset=extend_offset, kv_cs=kv_cs,
                                    paged=paged)
            y = residual_cs(y)
            nc = {k: nc[k] for k in _LAYER_CACHE_KEYS if k in nc}
            return y, (nc, touched)

        policies = {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        }
        body_fn = jax.checkpoint(body, policy=policies[remat_policy]) \
            if (remat and mode == "train") else body

        x, (new_layer_cache, touched) = jax.lax.scan(
            body_fn, x, (stacked, layer_cache), unroll=unroll_layers)

    fn_params = {k: v for k, v in params.items() if k.startswith("final_norm")}
    x = L.apply_norm(cfg.norm_type, x, _norm_p(fn_params, "final_norm"),
                     cfg.eps)

    if last_only:
        x = x[:, -1:]
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(cd)
    logits = logits_cs(logits)

    new_cache = None
    if mode != "train" and cache is not None:
        new_cache = dict(new_layer_cache)
        if touched is not None and paged is not None:
            new_cache["expert_loads"] = touched
        if mode == "decode":
            lc = cache["k"].shape[2] if "k" in cache else 0
            if slot_pos is not None:
                if row_idx is not None:
                    hit = jnp.arange(lc)[None, :] == (row_idx % lc)[:, None]
                else:
                    hit = (jnp.arange(lc) == idx % lc)[None, :]
                new_cache["slot_pos"] = jnp.where(hit, positions[:, :1],
                                                  slot_pos)
            if row_idx is not None:
                new_cache["row_idx"] = row_idx + 1
            new_cache["idx"] = idx + 1
        else:  # prefill
            S = positions.shape[1]
            off = extend_offset
            if slot_pos is not None:
                lc = cache["k"].shape[2]
                if off > 0:
                    pad = jnp.full((positions.shape[0], lc - off - S), -1,
                                   jnp.int32)
                    new_cache["slot_pos"] = jnp.concatenate(
                        [slot_pos[:, :off], positions, pad], axis=1)
                elif S >= lc:
                    last = positions[:, S - lc:]
                    new_cache["slot_pos"] = jnp.roll(last, S % lc, axis=1)
                else:
                    pad = jnp.full((positions.shape[0], lc - S), -1, jnp.int32)
                    new_cache["slot_pos"] = jnp.concatenate([positions, pad], axis=1)
            new_cache["idx"] = jnp.int32(off + S)
    return logits, new_cache


def lm_loss(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
            mask: jax.Array) -> jax.Array:
    """Cross-entropy over the (padded) vocab; labels are < vocab_size so
    padded logit columns never receive probability mass via the label path —
    they only inflate the partition function, which is fine at init and
    irrelevant for roofline purposes."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    true = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = (lse - true) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
