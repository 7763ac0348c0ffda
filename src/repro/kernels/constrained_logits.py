"""Pallas TPU fused constrained-sampling kernel (iPDB §5.2 grammar-forced
generation, TPU-adapted).

The grammar automaton (host-side, O(bytes)) produces a per-step vocab mask.
Naively applying it costs 3–4 HBM sweeps over (B, V): mask-select,
temperature-scale, add Gumbel noise, argmax. This kernel fuses all four
into ONE streamed pass: grid = (B, V/bv) with the vocab axis sequential and
a running (best value, best index) pair in VMEM scratch.

Greedy decoding = zero Gumbel noise. Temperature is folded into the
comparison key. This is the per-decode-step hot path of the PREDICT
operator when structured output is enforced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(logits_ref, mask_ref, noise_ref, out_ref,
            best_ref, idx_ref, *, inv_temp: float, block_v: int, nv: int):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    x = logits_ref[0].astype(jnp.float32) * inv_temp      # (1, bv)
    x = x + noise_ref[0].astype(jnp.float32)
    ok = mask_ref[0] != 0
    x = jnp.where(ok, x, NEG_INF)

    # first index of the block maximum, as (1, 1) vectors: argmax and
    # dynamic indexing do not lower on the TPU, two reductions do
    local_v = jnp.max(x, axis=-1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    local_i = jnp.min(jnp.where(x == local_v, iota, block_v), axis=-1,
                      keepdims=True)
    better = local_v > best_ref[...]
    best_ref[...] = jnp.where(better, local_v, best_ref[...])
    idx_ref[...] = jnp.where(better, vb * block_v + local_i, idx_ref[...])

    @pl.when(vb == nv - 1)
    def _finalize():
        out_ref[0] = idx_ref[...]


def constrained_sample_pallas(logits, mask, noise, *, temperature: float = 1.0,
                              block_v: int = 2048, interpret: bool = False):
    """logits (B, V) fp; mask (B, V) int8/bool (1 = allowed); noise (B, V)
    fp32 Gumbel noise (zeros → greedy). Returns sampled token ids (B,) int32
    = argmax(mask ? logits/T + noise : -inf).

    Rows ride as (B, 1, V) so each block's last two dims are (1, bv) with 1
    the full middle dim — the TPU tiling rule."""
    B, V = logits.shape
    assert V % block_v == 0, (V, block_v)
    nv = V // block_v
    inv_temp = 1.0 / max(temperature, 1e-6)

    kern = functools.partial(_kernel, inv_temp=inv_temp, block_v=block_v,
                             nv=nv)
    row = pl.BlockSpec((1, 1, block_v), lambda b, j: (b, 0, j))
    out = pl.pallas_call(
        kern,
        grid=(B, nv),
        in_specs=[row, row, row],
        out_specs=pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(logits.reshape(B, 1, V), mask.reshape(B, 1, V), noise.reshape(B, 1, V))
    return out[:, 0, 0]
