"""Pallas TPU decode attention: one query token per sequence against a long
KV cache. Memory-bound by the cache read — the kernel's job is to stream
k/v blocks through VMEM exactly once with the streamed-softmax accumulator
in scratch.

Two layouts:
  * dense — per-sequence contiguous (ring-buffered) caches,
    grid = (B·KV, num_cache_blocks), cache axis innermost/sequential.
  * paged — a global pool of fixed-size KV pages addressed through a
    per-sequence block table (scalar-prefetched so the BlockSpec index_map
    can chase page ids), grid = (B·KV, num_table_blocks) where the caller
    sizes num_table_blocks to the batch's ACTUAL fill, not max_len.
    Inactive trailing table entries are expected to repeat the last active
    page id (same index ⇒ the pipeline skips the re-fetch) and contribute
    nothing: compute is predicated off for them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, spos_ref, q_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, *, scale: float, nl: int):
    lb = pl.program_id(1)

    @pl.when(lb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qpos_ref[0]                                   # (1, 1) this sequence
    spos = spos_ref[0]                                   # (1, bl) slot positions

    q = q_ref[0].astype(jnp.float32) * scale             # (G, D)
    k = k_ref[0].astype(jnp.float32)                     # (bl, D)
    v = v_ref[0].astype(jnp.float32)                     # (bl, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (G, bl)
    ok = (spos >= 0) & (spos <= qpos)                    # (1, bl)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + \
        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(lb == nl - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, slot_positions, q_position,
                            *, block_l: int = 512, interpret: bool = False):
    """q (BK, G, D); k_cache, v_cache (BK, L, D); slot_positions (BK, L);
    q_position (BK, 1). Returns (BK, G, D).

    The position arrays ride as (BK, 1, X) so each block's last two dims
    are (1, X) with 1 the full middle dim — the TPU tiling rule."""
    BK, G, D = q.shape
    L = k_cache.shape[1]
    assert L % block_l == 0, (L, block_l)
    nl = L // block_l
    scale = 1.0 / math.sqrt(D)

    kern = functools.partial(_kernel, scale=scale, nl=nl)
    out = pl.pallas_call(
        kern,
        grid=(BK, nl),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),       # q_position
            pl.BlockSpec((1, 1, block_l), lambda b, j: (b, 0, j)),  # slot pos
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_l, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_l, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(q_position.reshape(BK, 1, 1), slot_positions.reshape(BK, 1, L),
      q, k_cache, v_cache)
    return out


# ------------------------------- paged layout ---------------------------------
def _paged_kernel(bt_ref, nact_ref, qpos_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, ps: int,
                  nb: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nact_ref[b])
    def _block():
        qpos = qpos_ref[b]
        q = q_ref[0].astype(jnp.float32) * scale             # (G, D)
        k = k_ref[0].astype(jnp.float32)                     # (ps, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (G, ps)
        # paged layout invariant: logical slot index == absolute position,
        # so validity needs no per-slot position array — just the fill level
        tok = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jnp.where(tok <= qpos, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def decode_attention_paged_pallas(q, k_pool, v_pool, block_tables,
                                  num_active, q_position, *,
                                  scale: float = None,
                                  interpret: bool = False):
    """q (BK, G, D); k_pool, v_pool (P, ps, D) global page pools;
    block_tables (BK, NB) int32 page ids (must be valid pool indices — the
    wrapper clamps); num_active (BK,) active blocks per sequence;
    q_position (BK,). Returns (BK, G, D).

    The block table, fill counts and query positions are scalar-prefetched
    so the k/v BlockSpec index_map dereferences the table: block j of
    sequence b is fetched from pool page block_tables[b, j] — the kernel
    reads shared (e.g. instruction-prefix) pages in place, no gather.

    scale overrides the softmax scale (default 1/sqrt(D)): when D is the
    zero-padded lane width the caller passes 1/sqrt(true head_dim) — the
    padded lanes contribute 0 to the dot so no q-side compensation is
    needed."""
    BK, G, D = q.shape
    P, ps, _ = k_pool.shape
    NB = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kern = functools.partial(_paged_kernel, scale=scale, ps=ps, nb=NB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BK, NB),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j, bt, na, qp: (b, 0, 0)),
            pl.BlockSpec((1, ps, D), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, ps, D), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j, bt, na, qp: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BK, G, D), q.dtype),
        interpret=interpret,
    )(block_tables, num_active, q_position, q, k_pool, v_pool)
    return out


# --------------------------- paged layout, int8 pages --------------------------
def _paged_quant_kernel(bt_ref, nact_ref, qpos_ref, q_ref, k_ref, v_ref,
                        kq_ref, vq_ref, ks_ref, vs_ref, fl_ref,
                        o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                        ps: int, nb: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nact_ref[b])
    def _block():
        qpos = qpos_ref[b]
        q = q_ref[0].astype(jnp.float32) * scale             # (G, D)
        # frozen pages read the int8 shadow × per-page scale; live pages
        # read the fp pool — both blocks arrive via the same table-chased
        # index_map, the select is pure VPU work
        frozen = fl_ref[0] > 0                               # (1, 1)
        k = jnp.where(frozen,
                      kq_ref[0].astype(jnp.float32) * ks_ref[0],
                      k_ref[0].astype(jnp.float32))          # (ps, D)
        v = jnp.where(frozen,
                      vq_ref[0].astype(jnp.float32) * vs_ref[0],
                      v_ref[0].astype(jnp.float32))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (G, ps)
        tok = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jnp.where(tok <= qpos, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def decode_attention_paged_quant_pallas(q, k_pool, v_pool, kq_pool, vq_pool,
                                        kscale, vscale, quant_flags,
                                        block_tables, num_active, q_position,
                                        *, scale: float = None,
                                        interpret: bool = False):
    """Quant-aware twin of decode_attention_paged_pallas: kq_pool/vq_pool
    (P, ps, D) int8 shadow pools; kscale/vscale (P, 1) float32 per-page
    scales; quant_flags (P, 1) int32 (>0 ⇒ page is frozen/quantized).
    Remaining arguments and the streamed-softmax structure are identical —
    the only delta is a per-page dequant select on the fetched block.
    Scales and flags ride as (P, 1, 1) so their (1, 1) blocks span the
    full last two dims, as the TPU tiling rule requires."""
    BK, G, D = q.shape
    P, ps, _ = k_pool.shape
    NB = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kern = functools.partial(_paged_quant_kernel, scale=scale, ps=ps, nb=NB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BK, NB),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j, bt, na, qp: (b, 0, 0)),
            pl.BlockSpec((1, ps, D), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, ps, D), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, ps, D), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, ps, D), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j, bt, na, qp: (bt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j, bt, na, qp: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BK, G, D), q.dtype),
        interpret=interpret,
    )(block_tables, num_active, q_position, q, k_pool, v_pool,
      kq_pool, vq_pool, kscale.reshape(P, 1, 1), vscale.reshape(P, 1, 1),
      quant_flags.reshape(P, 1, 1))
    return out
