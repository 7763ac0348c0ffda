"""Jit'd public wrappers around the Pallas kernels.

These own the layout plumbing (GQA head folding, D-padding to 128, expert
sort + group padding) so model code can call them with natural shapes. On
the CPU they run with interpret=True; on TPU, interpret=False
compiles the real Mosaic kernels. `use_interpret()` resolves the default
from the backend.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.constrained_logits import constrained_sample_pallas
from repro.kernels.decode_attention import (
    decode_attention_paged_pallas, decode_attention_paged_quant_pallas,
    decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import gmm_pallas
from repro.kernels.selective_scan import selective_scan_pallas


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_axis(x, axis, to, value=0):
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ------------------------------ flash attention -------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "window", "prefix_len",
                                             "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, q_positions, kv_positions, *, causal=True,
                    window=0, prefix_len=0, block_q=256, block_kv=512,
                    interpret: Optional[bool] = None):
    """Natural shapes: q (B, Sq, H, D); k, v (B, Skv, KV, D); positions
    (B, S). Folds GQA into (B·KV) kernel batches, pads Sq/Skv to block
    multiples and D to 128."""
    interpret = use_interpret() if interpret is None else interpret
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    Dp = _round_up(D, 128)
    Sqp, Skvp = _round_up(Sq, block_q), _round_up(Skv, block_kv)

    qf = _pad_axis(_pad_axis(q, 3, Dp), 1, Sqp)
    kf = _pad_axis(_pad_axis(k, 3, Dp), 1, Skvp)
    vf = _pad_axis(_pad_axis(v, 3, Dp), 1, Skvp)
    qp = _pad_axis(q_positions, 1, Sqp, value=-1)
    kp = _pad_axis(kv_positions, 1, Skvp, value=-1)

    # (B, S, KV, G, D) → (B, KV, G, S, D) → (B·KV, G, S, D)
    qr = qf.reshape(B, Sqp, KV, G, Dp).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KV, G, Sqp, Dp)
    kr = kf.transpose(0, 2, 1, 3).reshape(B * KV, Skvp, Dp)
    vr = vf.transpose(0, 2, 1, 3).reshape(B * KV, Skvp, Dp)
    qpr = jnp.repeat(qp, KV, axis=0)
    kpr = jnp.repeat(kp, KV, axis=0)

    # scale correction: kernel scales by 1/sqrt(Dp); compensate to 1/sqrt(D)
    qr = qr * jnp.asarray((Dp / D) ** 0.5, qr.dtype)

    o = flash_attention_pallas(qr, kr, vr, qpr, kpr, causal=causal,
                               window=window, prefix_len=prefix_len,
                               block_q=block_q, block_kv=block_kv,
                               interpret=interpret)
    o = o.reshape(B, KV, G, Sqp, Dp).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sqp, H, Dp)
    return o[:, :Sq, :, :D]


# ------------------------------ decode attention ------------------------------
@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def decode_attention(q, k_cache, v_cache, slot_positions, q_position, *,
                     block_l=512, interpret: Optional[bool] = None):
    """q (B, H, D); caches (B, L, KV, D); slot_positions (B, L);
    q_position (B,). Returns (B, H, D)."""
    interpret = use_interpret() if interpret is None else interpret
    B, H, D = q.shape
    _, L, KV, _ = k_cache.shape
    G = H // KV
    Dp = _round_up(D, 128)
    Lp = _round_up(L, block_l)

    qf = _pad_axis(q, 2, Dp).reshape(B, KV, G, Dp).reshape(B * KV, G, Dp)
    kf = _pad_axis(_pad_axis(k_cache, 3, Dp), 1, Lp) \
        .transpose(0, 2, 1, 3).reshape(B * KV, Lp, Dp)
    vf = _pad_axis(_pad_axis(v_cache, 3, Dp), 1, Lp) \
        .transpose(0, 2, 1, 3).reshape(B * KV, Lp, Dp)
    sp = jnp.repeat(_pad_axis(slot_positions, 1, Lp, value=-1), KV, axis=0)
    qpos = jnp.repeat(q_position[:, None], KV, axis=0).reshape(B * KV, 1)

    qf = qf * jnp.asarray((Dp / D) ** 0.5, qf.dtype)
    o = decode_attention_pallas(qf, kf, vf, sp, qpos, block_l=block_l,
                                interpret=interpret)
    return o.reshape(B, KV, G, Dp).reshape(B, H, Dp)[..., :D]


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def decode_attention_paged(q, k_pool, v_pool, block_tables, q_position, *,
                           head_dim: Optional[int] = None, quant=None,
                           layer=None, interpret: Optional[bool] = None):
    """Paged decode attention over the pre-folded pool layout: q (B, H, D);
    pools (KV, P, ps, Dp) with Dp = head_dim already zero-padded to the
    128-lane width, so the kernel's (KV·P, ps, Dp) view is a FREE reshape —
    no per-step transpose or pad.  block_tables (B, NB) int32 page ids
    (-1 = invalid); q_position (B,). Returns (B, H, D).

    GQA folding duplicates only the tiny block table — the pool itself is
    addressed per kv-head slice, not per batch row.  Inactive/invalid table
    entries are rewritten to the row's last active page so the kernel
    pipeline revisits an already-resident page (no extra DMA) while the
    predicated body skips the compute.  The softmax scale is 1/sqrt(true
    head_dim): padded lanes are zero on both q and k, so they drop out of
    the dot with no q-side compensation.

    quant (dict or None): int8 shadow pools "kq"/"vq" (KV, P, ps, Dp),
    per-page scales "kscale"/"vscale" (KV, P) and frozen flags "flags"
    (P,) — dispatches to the dequantizing kernel twin.

    With `layer`, the pools, shadow pools and scales are stacked over
    layers on a leading axis: the kernel reads the pages of `layer` through
    the same free view of the whole stack, offset by the layer's pages."""
    interpret = use_interpret() if interpret is None else interpret
    B, H, D = q.shape
    KV, P, ps, Dp = k_pool.shape[-4:]
    D = head_dim or D
    NB = block_tables.shape[1]
    G = H // KV

    qf = _pad_axis(q, 2, Dp).reshape(B, KV, G, Dp).reshape(B * KV, G, Dp)
    kf = k_pool.reshape(-1, ps, Dp)
    vf = v_pool.reshape(-1, ps, Dp)

    qpos = q_position.astype(jnp.int32)
    nact = jnp.clip(jnp.clip(qpos, 0, None) // ps + 1, 1, NB)       # (B,)
    last = jnp.take_along_axis(block_tables, (nact - 1)[:, None], axis=1)
    idxs = jnp.arange(NB, dtype=jnp.int32)[None, :]
    bt = jnp.where((idxs < nact[:, None]) & (block_tables >= 0),
                   block_tables, last)
    bt = jnp.clip(bt, 0, P - 1)
    if layer is not None:
        bt = bt + layer * KV * P
    btf = (bt[:, None, :] +
           jnp.arange(KV, dtype=jnp.int32)[None, :, None] * P
           ).reshape(B * KV, NB)
    nactf = jnp.repeat(nact, KV)
    qposf = jnp.repeat(qpos, KV)

    scale = 1.0 / (D ** 0.5)
    if quant is not None:
        kqf = quant["kq"].reshape(-1, ps, Dp)
        vqf = quant["vq"].reshape(-1, ps, Dp)
        ksf = quant["kscale"].reshape(-1, 1).astype(jnp.float32)
        vsf = quant["vscale"].reshape(-1, 1).astype(jnp.float32)
        flf = jnp.tile(quant["flags"].astype(jnp.int32)[None, :],
                       (ksf.shape[0] // P, 1)).reshape(-1, 1)
        o = decode_attention_paged_quant_pallas(
            qf, kf, vf, kqf, vqf, ksf, vsf, flf, btf, nactf, qposf,
            scale=scale, interpret=interpret)
    else:
        o = decode_attention_paged_pallas(qf, kf, vf, btf, nactf, qposf,
                                          scale=scale, interpret=interpret)
    return o.reshape(B, KV, G, Dp).reshape(B, H, Dp)[..., :D]


# --------------------------------- MoE gmm ------------------------------------
@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def gmm(x, w, group_sizes, *, block_m=128, block_n=128, block_k=256,
        interpret: Optional[bool] = None):
    """Grouped matmul: x (T, M) rows sorted by expert; w (E, M, N);
    group_sizes (E,) sums to T. Pads each group to a block_m multiple via a
    scatter, runs the kernel, gathers back. Returns (T, N)."""
    interpret = use_interpret() if interpret is None else interpret
    T, M = x.shape
    E, _, N = w.shape
    Mp, Np = _round_up(M, block_k), _round_up(N, block_n)

    gs = group_sizes.astype(jnp.int32)
    padded_sizes = ((gs + block_m - 1) // block_m) * block_m
    # worst case every expert pads to a full extra block
    Tp = _round_up(T, block_m) + E * block_m
    src_start = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(gs)[:-1]])
    dst_start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                 jnp.cumsum(padded_sizes)[:-1]])
    # destination row for each source row
    eid = jnp.repeat(jnp.arange(E, dtype=jnp.int32), gs, total_repeat_length=T)
    offset_in_group = jnp.arange(T, dtype=jnp.int32) - src_start[eid]
    dst = dst_start[eid] + offset_in_group
    xp = jnp.zeros((Tp, Mp), x.dtype).at[dst].set(_pad_axis(x, 1, Mp))

    # per-row-block expert ids
    nblocks = Tp // block_m
    block_starts = jnp.arange(nblocks, dtype=jnp.int32) * block_m
    dst_end = dst_start + padded_sizes
    block_eid = jnp.clip(jnp.searchsorted(dst_end, block_starts, side="right"),
                         0, E - 1).astype(jnp.int32)

    wp = _pad_axis(_pad_axis(w, 1, Mp), 2, Np)
    out = gmm_pallas(xp, wp, block_eid, block_m=block_m, block_n=block_n,
                     block_k=block_k, interpret=interpret)
    return out[dst][:, :N]


# --------------------------- constrained sampling -----------------------------
@functools.partial(jax.jit, static_argnames=("temperature", "block_v",
                                             "interpret"))
def constrained_sample(logits, mask, noise=None, *, temperature=1.0,
                       block_v=2048, interpret: Optional[bool] = None):
    """argmax(mask ? logits/T + noise : -inf) over the vocab, one HBM pass.
    noise=None → greedy."""
    interpret = use_interpret() if interpret is None else interpret
    B, V = logits.shape
    Vp = _round_up(V, block_v)
    lf = _pad_axis(logits, 1, Vp, value=-1e30)
    mf = _pad_axis(mask.astype(jnp.int8), 1, Vp)
    nf = jnp.zeros((B, Vp), jnp.float32) if noise is None \
        else _pad_axis(noise.astype(jnp.float32), 1, Vp)
    return constrained_sample_pallas(lf, mf, nf, temperature=temperature,
                                     block_v=block_v, interpret=interpret)


# ------------------------------ selective scan --------------------------------
@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def selective_scan(u, dt, A, B, C, D, *, chunk=256, block_d=128,
                   interpret: Optional[bool] = None):
    """Shapes as repro.models.mamba.selective_scan with h0=0. Pads S to a
    chunk multiple and Di to block_d."""
    interpret = use_interpret() if interpret is None else interpret
    Bz, S, Di = u.shape
    Sp = _round_up(S, chunk)
    Dp = _round_up(Di, block_d)
    uf = _pad_axis(_pad_axis(u, 1, Sp), 2, Dp)
    dtf = _pad_axis(_pad_axis(dt, 1, Sp), 2, Dp)
    Af = _pad_axis(A, 0, Dp)
    Bf = _pad_axis(B, 1, Sp)
    Cf = _pad_axis(C, 1, Sp)
    Df = _pad_axis(D, 0, Dp)
    y, h = selective_scan_pallas(uf, dtf, Af, Bf, Cf, Df, chunk=chunk,
                                 block_d=block_d, interpret=interpret)
    return y[:, :S, :Di], h[:, :Di]
