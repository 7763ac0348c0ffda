"""Mixture-of-experts block: token-choice top-k routing with no dropped
token, routed experts as one grouped matmul, and optional shared experts.

Every token's top-k choices are sorted by expert and the sorted rows run
through each expert's SwiGLU as a grouped matmul (`jax.lax.ragged_dot`,
which lowers to a grouped-matmul kernel on the TPU and to a masked matmul
elsewhere), so an expert reads its weights once for all the rows routed to
it and no choice is ever dropped for capacity.  The rows are then weighted
by their gates and summed back per token.

Two routing rules (`norm_topk_prob`):
  True  — softmax over the top-k router logits (Mixtral, Qwen3-MoE);
  False — the top-k of a softmax over all experts, not renormalised
          (DeepSeek-V2).
Shared experts run on every token as one SwiGLU of their summed width.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Identity = lambda x: x


def route(x: jax.Array, router: jax.Array, top_k: int,
          norm_topk_prob: bool) -> Tuple[jax.Array, jax.Array]:
    """(gates (T, K) float32, expert ids (T, K)) of tokens x (T, M)."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    if norm_topk_prob:
        top, idx = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top, axis=-1), idx
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)


def experts_touched(idx: jax.Array, num_experts: int,
                    valid: jax.Array) -> jax.Array:
    """Distinct experts the valid tokens (T,) chose, from ids (T, K)."""
    hit = jnp.zeros((num_experts,), jnp.int32).at[idx].max(
        jnp.broadcast_to(valid[:, None], idx.shape).astype(jnp.int32))
    return hit.sum()


def moe_block(x: jax.Array, params: dict, *, num_experts: int, top_k: int,
              norm_topk_prob: bool = True,
              compute_dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """x (T, M) token-major. params: router (M, E), w_gate/w_up (E, M, F),
    w_down (E, F, M), and with shared experts shared_gate/shared_up
    (M, S·F), shared_down (S·F, M).  Returns (y (T, M), expert ids (T, K))."""
    T, M = x.shape
    with jax.named_scope("moe.route"):
        gates, idx = route(x, params["router"], top_k, norm_topk_prob)
        flat = idx.reshape(-1)                                   # (T·K,)
        order = jnp.argsort(flat, stable=True)
        rows = order // top_k                                    # token of row
        sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
        xs = x.astype(compute_dtype)[rows]                       # (T·K, M)
    with jax.named_scope("moe.experts"):
        wg = params["w_gate"].astype(compute_dtype)
        wu = params["w_up"].astype(compute_dtype)
        wd = params["w_down"].astype(compute_dtype)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, wg, sizes)) * \
            jax.lax.ragged_dot(xs, wu, sizes)
        out = jax.lax.ragged_dot(h, wd, sizes,
                                 preferred_element_type=jnp.float32)
        w = gates.reshape(-1)[order]
        y = jnp.zeros((T, M), jnp.float32).at[rows].add(out * w[:, None])
        if "shared_gate" in params:
            xc = x.astype(compute_dtype)
            hs = jax.nn.silu(xc @ params["shared_gate"].astype(compute_dtype)) \
                * (xc @ params["shared_up"].astype(compute_dtype))
            y = y + (hs @ params["shared_down"].astype(compute_dtype)
                     ).astype(jnp.float32)
    return y.astype(x.dtype), idx


def moe_block_reference(x, params, *, num_experts, top_k,
                        norm_topk_prob: bool = True, **_):
    """Oracle: every expert on every token in float32, then the gated sum
    of each token's top-k (and the shared experts)."""
    gates, idx = route(x, params["router"], top_k, norm_topk_prob)
    xf = x.astype(jnp.float32)
    wg = params["w_gate"].astype(jnp.float32)
    wu = params["w_up"].astype(jnp.float32)
    wd = params["w_down"].astype(jnp.float32)
    h = jax.nn.silu(jnp.einsum("tm,emh->teh", xf, wg))
    h = h * jnp.einsum("tm,emh->teh", xf, wu)
    all_out = jnp.einsum("teh,ehm->tem", h, wd)                 # (T, E, M)
    sel = jnp.take_along_axis(all_out, idx[..., None], axis=1)  # (T, K, M)
    y = (sel * gates[..., None]).sum(axis=1)
    if "shared_gate" in params:
        hs = jax.nn.silu(xf @ params["shared_gate"].astype(jnp.float32)) * \
            (xf @ params["shared_up"].astype(jnp.float32))
        y = y + hs @ params["shared_down"].astype(jnp.float32)
    return y.astype(x.dtype)
