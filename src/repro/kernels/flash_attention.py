"""Pallas TPU flash attention (prefill): causal / sliding-window / prefix-LM,
GQA-native.

Grid = (B·KV, num_q_blocks, num_kv_blocks); the kv axis is innermost and
sequential ("arbitrary"), accumulating the streamed softmax in VMEM scratch
(m, l, acc). Block shapes are explicit BlockSpecs sized for ~16 MiB VMEM:
q (G, bq, D), k/v (bk, D) with bq/bk multiples of 128 and D padded to a
multiple of 128 in ops.py (hubert's D=80 → 128).

Fully-masked (q-block, kv-block) pairs (beyond the causal frontier or
outside the sliding window) are skipped with pl.when — on hardware that
saves the MXU work; the HBM fetch is already minimized by the BlockSpec.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref,   # inputs
            o_ref,                                     # outputs
            m_ref, l_ref, acc_ref,                     # scratch
            *, scale: float, causal: bool, window: int, prefix_len: int,
            nk: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qpos_ref[0]                                  # (bq, 1) column
    kpos = kpos_ref[0]                                  # (1, bk) row

    # block-level skip: whole block beyond causal frontier / outside window
    qmax = jnp.max(qpos)
    qmin = jnp.min(qpos)
    kmin = jnp.min(jnp.where(kpos >= 0, kpos, jnp.iinfo(jnp.int32).max))
    kmax = jnp.max(kpos)
    live = kmax >= 0
    if causal:
        live &= kmin <= qmax
        if window > 0:
            live &= kmax > qmin - window
        if prefix_len > 0:
            live |= (kmax >= 0) & (kmin < prefix_len)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (G, bq, D)
        k = k_ref[0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0].astype(jnp.float32)                # (bk, D)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())))  # (G,bq,bk)

        valid = kpos >= 0                                # (1, bk)
        if causal:
            ok = valid & (kpos <= qpos)                  # (bq, bk)
            if window > 0:
                ok &= kpos > qpos - window
            if prefix_len > 0:
                ok |= valid & (kpos < prefix_len)
        else:
            ok = valid
        s = jnp.where(ok[None], s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + \
            jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, q_positions, kv_positions, *,
                           causal: bool = True, window: int = 0,
                           prefix_len: int = 0, block_q: int = 256,
                           block_kv: int = 512, interpret: bool = False):
    """q (BK, G, Sq, D); k, v (BK, Skv, D); q_positions (BK, Sq);
    kv_positions (BK, Skv). BK = batch × kv_heads (folded in ops.py).
    Returns (BK, G, Sq, D).

    Query positions ride as a (BK, Sq, 1) column and kv positions as a
    (BK, 1, Skv) row, so both blocks meet the TPU tiling rule and the mask
    broadcasts without a transpose."""
    BK, G, Sq, D = q.shape
    Skv = k.shape[1]
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv)
    nq, nk = Sq // block_q, Skv // block_kv
    scale = 1.0 / math.sqrt(D)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, prefix_len=prefix_len, nk=nk)
    grid = (BK, nq, nk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # qpos
            pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b, 0, j)),  # kpos
            pl.BlockSpec((1, G, block_q, D), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, block_q, D), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, G, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q_positions.reshape(BK, Sq, 1), kv_positions.reshape(BK, 1, Skv),
      q, k, v)
    return out
