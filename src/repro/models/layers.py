"""Shared neural-net layers: norms, RoPE, flash attention (custom VJP),
decode attention, MLPs.

The flash attention here is the pure-jnp oracle/production fallback: a
blockwise-streamed softmax identical in structure to the Pallas kernel in
`repro.kernels.flash_attention`. It carries a hand-written backward pass so
that neither forward nor backward ever materializes an (Sq × Skv) score
matrix — this is what makes the 32k/500k dry-run cells compile with sane
memory footprints.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# -- norms ---------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: Optional[jax.Array], eps: float = 1e-6
            ) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def layernorm(x: jax.Array, w: Optional[jax.Array], b: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(norm_type: str, x: jax.Array, params: Optional[dict],
               eps: float) -> jax.Array:
    if norm_type == "rmsnorm":
        return rmsnorm(x, params["scale"] if params else None, eps)
    if norm_type == "layernorm":
        return layernorm(x, params["scale"], params["bias"], eps)
    if norm_type == "nonparam_ln":
        return layernorm(x, None, None, eps)
    raise ValueError(norm_type)


# -- rotary embeddings ----------------------------------------------------------
def _yarn_dim(rotations: float, dim: int, base: float, max_pos: int) -> float:
    """The rotary dimension that turns ``rotations`` times over ``max_pos``."""
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) \
        / (2 * math.log(base))


def yarn_range(dim: int, base: float, max_pos: int, beta_fast: float,
               beta_slow: float) -> Tuple[int, int]:
    """(low, high): frequencies below ``low`` keep their period, those above
    ``high`` are interpolated; a linear ramp in between (YaRN,
    arXiv:2309.00071)."""
    low = math.floor(_yarn_dim(beta_fast, dim, base, max_pos))
    high = math.ceil(_yarn_dim(beta_slow, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(dim: int, theta: float, yarn=None) -> jax.Array:
    """(dim // 2,) inverse frequencies; ``yarn`` (factor, original max
    positions, beta_fast, beta_slow) blends interpolated and original
    frequencies along YaRN's ramp."""
    half = dim // 2
    extra = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    if not yarn:
        return extra
    factor, max_pos, beta_fast, beta_slow = yarn
    low, high = yarn_range(dim, theta, max_pos, beta_fast, beta_slow)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: Optional[jax.Array] = None) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (B, S) absolute positions.
    Rotates the halves (i, i + D/2) with frequency i; ``inv_freq``
    overrides the plain ``theta`` frequencies."""
    d = x.shape[-1]
    half = d // 2
    freqs = rope_inv_freq(d, theta) if inv_freq is None else inv_freq
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- flash attention (blockwise, custom VJP) ------------------------------------
def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _block_mask(qpos, kpos, causal: bool, window: int, prefix_len: int = 0):
    """qpos (Bq,), kpos (Bk,) → (Bq, Bk) bool mask of VALID entries.
    prefix_len > 0 gives prefix-LM masking: positions < prefix_len are
    bidirectionally visible (PaliGemma-style image+prefix block)."""
    present = kpos[None, :] >= 0
    valid = present
    if causal:
        valid = present & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            valid &= kpos[None, :] > qpos[:, None] - window
        if prefix_len > 0:
            valid |= present & (kpos[None, :] < prefix_len)
    return valid


def _scan_map(f, xs, unroll):
    """lax.map with an unroll option (cost-calibration compiles unroll so
    XLA's HloCostAnalysis sees every iteration)."""
    def body(_, x):
        return None, f(x)
    _, ys = jax.lax.scan(body, None, xs, unroll=unroll)
    return ys


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention(q, k, v, q_positions, kv_positions,
                    causal: bool = True, window: int = 0,
                    prefix_len: int = 0,
                    block_q: int = 512, block_kv: int = 1024,
                    unroll: bool = False, banded: bool = False):
    """Blockwise attention. q (B,Sq,H,D); k,v (B,Skv,KV,D); GQA via H = KV*G.
    positions are absolute (used for RoPE-independent masking); kv position
    -1 marks padding. Returns (B, Sq, H, D) in q.dtype.
    """
    out, _ = _flash_fwd(q, k, v, q_positions, kv_positions,
                        causal, window, prefix_len, block_q, block_kv,
                        unroll, banded)
    return out


def _flash_fwd(q, k, v, q_positions, kv_positions, causal, window, prefix_len,
               block_q, block_kv, unroll=False, banded=False):
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(D)

    qp = _pad_to(q, 1, block_q)
    qpos = _pad_to(q_positions, 1, block_q, value=-1)
    kp = _pad_to(k, 1, block_kv)
    vp = _pad_to(v, 1, block_kv)
    kpos = _pad_to(kv_positions, 1, block_kv, value=-1)
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_kv

    qb = qp.reshape(B, nq, block_q, KV, G, D).astype(jnp.float32) * scale
    kb = kp.reshape(B, nk, block_kv, KV, D).astype(jnp.float32)
    vb = vp.reshape(B, nk, block_kv, KV, D).astype(jnp.float32)
    qposb = qpos.reshape(B, nq, block_q)
    kposb = kpos.reshape(B, nk, block_kv)

    # Banded mode (causal sliding window): per q block only the
    # ceil((window+block_q)/block_kv)+1 kv blocks intersecting
    # [q_start - window, q_end] are touched — a 90%+ FLOP/byte cut for
    # long sequences with small windows (§Perf opt A).
    use_band = banded and causal and window > 0 and prefix_len == 0
    nkw = min(nk, (window + block_q + block_kv - 1) // block_kv + 1)

    def per_q_block(qblk, qpos_blk):
        # qblk (B, block_q, KV, G, D); qpos_blk (B, block_q)
        def inner(carry, kblk, vblk, kpos_blk, live):
            m, l, acc = carry
            s = jnp.einsum("bqkgd,bjkd->bkgqj", qblk, kblk)
            mask = jax.vmap(_block_mask, in_axes=(0, 0, None, None, None))(
                qpos_blk, kpos_blk, causal, window, prefix_len)  # (B, bq, bk)
            mask = mask & live
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bkgqj,bjkd->bkgqd", p, vblk)
            return (m_new, l_new, acc_new)

        m0 = jnp.full((B, KV, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, block_q, D), jnp.float32)

        if use_band:
            # min valid position across the whole block (pads excluded);
            # banded mode assumes near-uniform positions across the batch
            qmin = jnp.min(jnp.where(qpos_blk >= 0, qpos_blk, 2 ** 30))
            jb0 = jnp.clip((qmin - window) // block_kv, 0, nk - 1)

            def kv_step(carry, i):
                j = jnp.clip(jb0 + i, 0, nk - 1)
                live = (jb0 + i) <= (nk - 1)        # clamp guard: no dups
                kblk = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
                vblk = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
                kpos_blk = jax.lax.dynamic_index_in_dim(kposb, j, 1,
                                                        keepdims=False)
                return inner(carry, kblk, vblk, kpos_blk, live), None

            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(nkw), unroll=unroll)
        else:
            def kv_step(carry, xs):
                kblk, vblk, kpos_blk = xs
                return inner(carry, kblk, vblk, kpos_blk, True), None

            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0),
                (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
                 kposb.transpose(1, 0, 2)), unroll=unroll)
        l = jnp.maximum(l, 1e-30)
        o = acc / l[..., None]                      # (B, KV, G, bq, D)
        lse = m + jnp.log(l)                        # (B, KV, G, bq)
        return o, lse

    o_blocks, lse_blocks = _scan_map(
        lambda xs: per_q_block(*xs),
        (qb.transpose(1, 0, 2, 3, 4, 5), qposb.transpose(1, 0, 2)), unroll)
    # o_blocks (nq, B, KV, G, bq, D) → (B, Sq, H, D)
    o = o_blocks.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * block_q, H, D)
    lse = lse_blocks.transpose(1, 0, 4, 2, 3).reshape(B, nq * block_q, H)
    o = o[:, :Sq].astype(q.dtype)
    lse = lse[:, :Sq]
    return o, (q, k, v, q_positions, kv_positions, o, lse)


def _flash_bwd(causal, window, prefix_len, block_q, block_kv, unroll, banded,
               res, g):
    q, k, v, q_positions, kv_positions, o, lse = res
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(D)

    qp = _pad_to(q, 1, block_q).astype(jnp.float32)
    op = _pad_to(o, 1, block_q).astype(jnp.float32)
    gp = _pad_to(g, 1, block_q).astype(jnp.float32)
    lsep = _pad_to(lse, 1, block_q, value=0.0)
    qpos = _pad_to(q_positions, 1, block_q, value=-1)
    kp = _pad_to(k, 1, block_kv).astype(jnp.float32)
    vp = _pad_to(v, 1, block_kv).astype(jnp.float32)
    kpos = _pad_to(kv_positions, 1, block_kv, value=-1)
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_kv

    qb = qp.reshape(B, nq, block_q, KV, G, D)
    gb = gp.reshape(B, nq, block_q, KV, G, D)
    ob = op.reshape(B, nq, block_q, KV, G, D)
    lseb = lsep.reshape(B, nq, block_q, KV, G)   # lse laid out (B,S,H)→(...,KV,G)
    qposb = qpos.reshape(B, nq, block_q)
    kb = kp.reshape(B, nk, block_kv, KV, D)
    vb = vp.reshape(B, nk, block_kv, KV, D)
    kposb = kpos.reshape(B, nk, block_kv)

    # delta_i = rowsum(dO * O)
    delta = jnp.sum(gb * ob, axis=-1)            # (B, nq, bq, KV, G)

    def per_kv_block(kblk, vblk, kpos_blk):
        # accumulate dk, dv over all q blocks; also emit dq contribution
        def q_step(carry, xs):
            dk, dv = carry
            qblk, gblk, lse_blk, dlt_blk, qpos_blk = xs
            s = jnp.einsum("bqkgd,bjkd->bkgqj", qblk * scale, kblk)
            mask = jax.vmap(_block_mask, in_axes=(0, 0, None, None, None))(
                qpos_blk, kpos_blk, causal, window, prefix_len)
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            p = jnp.exp(s - lse_blk.transpose(0, 2, 3, 1)[..., None])  # (B,KV,G,bq,bk)
            dp = jnp.einsum("bqkgd,bjkd->bkgqj", gblk, vblk)
            ds = p * (dp - dlt_blk.transpose(0, 2, 3, 1)[..., None])
            dq_blk = jnp.einsum("bkgqj,bjkd->bqkgd", ds, kblk) * scale
            dk = dk + jnp.einsum("bkgqj,bqkgd->bjkd", ds, qblk * scale)
            dv = dv + jnp.einsum("bkgqj,bqkgd->bjkd", p, gblk)
            return (dk, dv), dq_blk

        dk0 = jnp.zeros((B, block_kv, KV, D), jnp.float32)
        dv0 = jnp.zeros((B, block_kv, KV, D), jnp.float32)
        (dk, dv), dq_parts = jax.lax.scan(
            q_step, (dk0, dv0),
            (qb.transpose(1, 0, 2, 3, 4, 5), gb.transpose(1, 0, 2, 3, 4, 5),
             lseb.transpose(1, 0, 2, 3, 4), delta.transpose(1, 0, 2, 3, 4),
             qposb.transpose(1, 0, 2)), unroll=unroll)
        return dk, dv, dq_parts  # dq_parts (nq, B, bq, KV, G, D)

    dk_blocks, dv_blocks, dq_sum = _scan_map(
        lambda xs: per_kv_block(*xs),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         kposb.transpose(1, 0, 2)), unroll)
    # dq: sum over kv blocks → (nq, B, bq, KV, G, D)
    dq = dq_sum.sum(axis=0).transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * block_q, H, D)
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, nk * block_kv, KV, D)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, nk * block_kv, KV, D)
    return (dq[:, :Sq].astype(q.dtype), dk[:, :Skv].astype(k.dtype),
            dv[:, :Skv].astype(v.dtype), None, None)


def _flash_fwd_rule(q, k, v, qpos, kpos, causal, window, prefix_len,
                    block_q, block_kv, unroll, banded):
    out, res = _flash_fwd(q, k, v, qpos, kpos, causal, window, prefix_len,
                          block_q, block_kv, unroll, banded)
    return out, res


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd)


# -- reference (naive) attention for tests --------------------------------------
def reference_attention(q, k, v, q_positions, kv_positions,
                        causal=True, window=0, prefix_len=0):
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgd,bjkd->bkgqj", qf, kf) / math.sqrt(D)
    mask = jax.vmap(_block_mask, in_axes=(0, 0, None, None, None))(
        q_positions, kv_positions, causal, window, prefix_len)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqj,bjkd->bqkgd", p, vf).reshape(B, Sq, H, D)
    return o.astype(q.dtype)


# -- decode attention (single query token vs. long KV cache) --------------------
def decode_attention(q, k_cache, v_cache, cache_positions, q_position, *,
                     scale: Optional[float] = None):
    """q (B, H, D); k_cache (B, L, KV, D); v_cache (B, L, KV, Dv), Dv may be
    less than D; cache_positions (B, L) absolute positions of each cache
    slot (-1 = empty); q_position (B,); ``scale`` defaults to D^-0.5.
    Returns (B, H, Dv). Pure jnp — the Pallas twin lives in
    kernels/decode_attention.
    """
    B, H, D = q.shape
    _, L, KV, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, D)
    qf = qf / math.sqrt(D) if scale is None else qf * scale
    s = jnp.einsum("bkgd,blkd->bkgl", qf, k_cache.astype(jnp.float32))
    valid = (cache_positions >= 0) & (cache_positions <= q_position[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,blkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, Dv).astype(q.dtype)


# -- paged decode attention (block-table addressed page pool) -------------------
def decode_attention_paged(q, k_pool, v_pool, block_tables, q_position, *,
                           head_dim=None, quant=None):
    """q (B, H, D); pools pre-folded (KV, P, ps, Dp) with Dp = head_dim
    zero-padded to the 128-lane width — a GLOBAL page pool shared by all
    sequences (and, for a shared instruction prefix, by all batch rows);
    block_tables (B, NB) int32 page ids (-1 = invalid entry); q_position
    (B,). Returns (B, H, D).

    Paged-layout invariant: logical slot index == absolute token position,
    so slot validity is just `index <= q_position` plus table-entry
    validity. quant (dict or None) carries int8 shadow pools "kq"/"vq"
    (KV, P, ps, Dp), per-page scales "kscale"/"vscale" (KV, P) and frozen
    flags "flags" (P,): frozen pages are read from the dequantized shadow,
    live pages from the fp pool. Gathered pages are sliced back to the true
    head_dim before the softmax so the math is bit-identical to the dense
    layout. Pure jnp (gathers the pages); the zero-gather Pallas twin
    lives in kernels/decode_attention.
    """
    B, H, D = q.shape
    KV, P, ps, Dp = k_pool.shape
    D = head_dim or D
    NB = block_tables.shape[1]
    safe = jnp.clip(block_tables, 0, P - 1)
    k = k_pool[:, safe]                               # (KV, B, NB, ps, Dp)
    v = v_pool[:, safe]
    if quant is not None:
        fl = (quant["flags"][safe] > 0)[None, :, :, None, None]
        kdq = (quant["kq"][:, safe].astype(jnp.float32)
               * quant["kscale"][:, safe][..., None, None]).astype(k.dtype)
        vdq = (quant["vq"][:, safe].astype(jnp.float32)
               * quant["vscale"][:, safe][..., None, None]).astype(v.dtype)
        k = jnp.where(fl, kdq, k)
        v = jnp.where(fl, vdq, v)
    k = k.transpose(1, 2, 3, 0, 4).reshape(B, NB * ps, KV, Dp)[..., :D]
    v = v.transpose(1, 2, 3, 0, 4).reshape(B, NB * ps, KV, Dp)[..., :D]
    pos = jnp.broadcast_to(jnp.arange(NB * ps, dtype=jnp.int32)[None],
                           (B, NB * ps))
    valid = jnp.repeat(block_tables >= 0, ps, axis=1)
    pos = jnp.where(valid, pos, -1)
    return decode_attention(q, k, v, pos, q_position)


def prefix_suffix_attention(q, k_prefix, v_prefix, k_suf, v_suf,
                            positions, prefix_len, *,
                            scale: Optional[float] = None):
    """Shared-prefix prefill attention WITHOUT replicating the prefix KV.

    q (B, S, H, D) suffix queries; k_prefix/v_prefix (Lp, KV, D) — ONE copy
    of the shared prefix KV, broadcast across the batch inside the einsum
    (no (B, Lp) materialization); k_suf/v_suf (B, S, KV, D) the suffix's
    own KV; positions (B, S) absolute (-1 = pad); prefix_len scalar number
    of valid prefix tokens (<= Lp). Prefix tokens are fully visible to
    every suffix query (their positions precede all suffix positions);
    the suffix part is causal. The two score blocks are merged with a
    joint streamed-softmax so the result equals one softmax over
    [prefix ++ suffix]. Values may be narrower than keys; ``scale``
    defaults to D^-0.5.
    """
    B, S, H, D = q.shape
    KV = k_suf.shape[2]
    Dv = v_suf.shape[-1]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, S, KV, G, D)
    qf = qf / math.sqrt(D) if scale is None else qf * scale

    ss = jnp.einsum("bskgd,btkd->bkgst", qf, k_suf.astype(jnp.float32))
    ok_s = (positions[:, None, :] >= 0) & \
           (positions[:, None, :] <= positions[:, :, None])       # (B, S, T)
    ss = jnp.where(ok_s[:, None, None], ss, NEG_INF)

    Lp = k_prefix.shape[0]
    if Lp:
        sp = jnp.einsum("bskgd,lkd->bkgsl", qf, k_prefix.astype(jnp.float32))
        ok_p = (jnp.arange(Lp)[None, None, :] < prefix_len) & \
               (positions[:, :, None] >= 0)                       # (B, S, Lp)
        sp = jnp.where(ok_p[:, None, None], sp, NEG_INF)
        m = jnp.maximum(sp.max(axis=-1), ss.max(axis=-1))         # (B,KV,G,S)
        pp = jnp.exp(sp - m[..., None])
        psx = jnp.exp(ss - m[..., None])
        denom = jnp.maximum(pp.sum(-1) + psx.sum(-1), 1e-30)
        o = jnp.einsum("bkgsl,lkd->bskgd", pp, v_prefix.astype(jnp.float32)) \
            + jnp.einsum("bkgst,btkd->bskgd", psx, v_suf.astype(jnp.float32))
    else:
        m = ss.max(axis=-1)
        psx = jnp.exp(ss - m[..., None])
        denom = jnp.maximum(psx.sum(-1), 1e-30)
        o = jnp.einsum("bkgst,btkd->bskgd", psx, v_suf.astype(jnp.float32))
    o = o / denom.transpose(0, 3, 1, 2)[..., None]
    return o.reshape(B, S, H, Dv).astype(q.dtype)


# -- MLPs ------------------------------------------------------------------------
def swiglu_mlp(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = jax.nn.gelu(x @ w_in + b_in)
    return h @ w_out + b_out
