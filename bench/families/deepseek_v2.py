"""The DeepSeek-V2 family (arXiv:2405.04434; the equations of
``modeling_deepseek.py`` in deepseek-ai/DeepSeek-V2-Lite): latent attention
without a query LoRA, YaRN rope on the rotary part, leading dense SwiGLU
layers, then DeepSeekMoE layers of routed experts chosen greedily from a
softmax over all of them (not renormalised) plus always-on shared experts.
The interface is the docstring of ``bench/families/dense.py``.

The weights follow the program's recipe for its random weights (one key per
leaf in sorted tree order, a stacked expert leaf drawn a layer at a time from
splits of its key, normal scaled by 1/sqrt(fan_in), norm scales one) and are
held as the program holds them, in the parameter dtype (bfloat16 as
published), so that the float32 forward pass reads exactly the served
values; it upcasts one layer at a time so that it fits where the program
did.  All norms are RMSNorm.  For hidden x, per layer:

    q = x W_q (heads, nope + rope); [c, k_pe] = x W_kva; c = RMSNorm(c)
    [k_nope, v] = c W_kvb (heads, nope + v)
    q_pe, k_pe rotated pairwise ((2i, 2i+1) at frequency i), YaRN freqs
    o = softmax((q_nope.k_nope + q_pe.k_pe) s) v, s = (nope+rope)^-0.5 m^2
    x += o W_o
    dense layer: x += SwiGLU(x)
    expert layer: p = softmax(x W_r); (w, idx) = top_k(p);
                  x += sum_j w_j E_idx_j(x) + S(x)

with m = 0.1 mscale_all_dim ln(factor) + 1.  Every matmul runs in float32
at ``HIGHEST`` precision, or for the control with both operands rounded to
float8 e4m3 (per-tensor scale) and float32 accumulation.
"""
# no `from __future__ import annotations`: the harness loads this file
# outside sys.modules, where a dataclass cannot resolve string annotations
import dataclasses
import functools
import json
import math
from typing import Dict, Iterable, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _mm, padded_vocab

KEYS = ("num_layers", "d_model", "num_heads", "head_dim", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff",
        "num_experts", "top_k", "num_shared_experts", "norm_topk_prob",
        "first_dense_layers", "dense_d_ff", "vocab_size", "tie_embeddings",
        "norm_eps", "yarn_factor", "yarn_original_max_pos", "yarn_beta_fast",
        "yarn_beta_slow", "yarn_mscale", "yarn_mscale_all_dim",
        "param_dtype")

_DT = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# -- weights --------------------------------------------------------------------
def _layer_leaves(cfg: dict, dense: bool) -> Dict[str, Tuple[int, ...]]:
    d, h, r = cfg["d_model"], cfg["num_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                   "v_head_dim"))
    out = {"ln_attn.scale": (d,), "attn.wq": (d, h, dn + dr),
           "attn.wkv_a": (d, r + dr), "attn.kv_norm.scale": (r,),
           "attn.wkv_b": (r, h, dn + dv), "attn.wo": (h, dv, d),
           "ln_mlp.scale": (d,)}
    if dense:
        f = cfg["dense_d_ff"]
        out.update({"mlp.w_gate": (d, f), "mlp.w_up": (d, f),
                    "mlp.w_down": (f, d)})
        return out
    e, f = cfg["num_experts"], cfg["d_ff"]
    fs = cfg["num_shared_experts"] * f
    out.update({"moe.router": (d, e), "moe.w_gate": (e, d, f),
                "moe.w_up": (e, d, f), "moe.w_down": (e, f, d)})
    if fs:
        out.update({"moe.shared_gate": (d, fs), "moe.shared_up": (d, fs),
                    "moe.shared_down": (fs, d)})
    return out


def _tree(cfg: dict) -> dict:
    """Leaf shapes in the nesting of the program's tree, layer leaves
    stacked: {"dense_layers": {...}, "layers": {...}, "embed", ...}."""
    d, vp, fd = cfg["d_model"], padded_vocab(cfg["vocab_size"]), \
        cfg["first_dense_layers"]
    tree = {"embed": (vp, d), "final_norm.scale": (d,),
            "layers": {k: (cfg["num_layers"] - fd,) + s
                       for k, s in _layer_leaves(cfg, False).items()}}
    if fd:
        tree["dense_layers"] = {k: (fd,) + s for k, s in
                                _layer_leaves(cfg, True).items()}
    if not cfg["tie_embeddings"]:
        tree["lm_head"] = (d, vp)
    return tree


def _fan_in(name: str, core: Tuple[int, ...]) -> int:
    if name in ("attn.wq", "attn.wkv_a", "attn.wkv_b"):
        return core[0]
    if name == "attn.wo":
        return core[0] * core[1]
    return core[-2]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_layers(key, shape, std: float, dtype):
    """A stacked expert leaf: layer i drawn from split i of ``key``, one
    layer's float32 draw live at a time."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], jnp.float32) * std
                   ).astype(dtype),
        jax.random.split(key, shape[0]))


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """``{"layers/<leaf>", "dense_layers/<leaf>", "embed", ...}`` in the
    parameter dtype, drawn leaf by leaf as the program draws them."""
    dt = _DT[cfg["param_dtype"]]
    paths, _ = jax.tree_util.tree_flatten_with_path(
        _tree(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(paths))
    out = {}
    for k, (path, shape) in zip(keys, paths):
        names = [p.key for p in path]
        leaf, stacked = names[-1], len(names) == 2
        core = shape[1:] if stacked else shape
        if len(core) == 1:                                   # norm scales
            out["/".join(names)] = jnp.ones(shape, dt)
            continue
        std = 1.0 / math.sqrt(max(1, _fan_in(leaf, core)))
        if stacked and leaf.startswith("moe."):
            v = _draw_layers(k, shape, std, dt)
        else:
            v = (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
        out["/".join(names)] = v
    return out


# -- forward ---------------------------------------------------------------------
def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _yarn(cfg: dict) -> Tuple[np.ndarray, float, float]:
    """(inverse frequencies, factor on cos and sin, softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    i = np.arange(dim // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / dim)
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    f = cfg["yarn_factor"]
    if not f:
        return extra, 1.0, scale

    def d(rot):
        return dim * math.log(cfg["yarn_original_max_pos"]
                              / (2 * math.pi * rot)) / (2 * math.log(base))

    def mscale(m):
        return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0

    low = max(math.floor(d(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(d(cfg["yarn_beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = extra / f * ramp + extra * (1.0 - ramp)
    m_all = mscale(cfg["yarn_mscale_all_dim"]) \
        if cfg["yarn_mscale_all_dim"] else 1.0
    return inv, mscale(cfg["yarn_mscale"]) / m_all, scale * m_all ** 2


def _rope_pairs(x, inv, factor):
    """Rotate the adjacent pairs (2i, 2i+1) of x (n, ..., dim) at
    frequency i, position = index along the first axis."""
    n = x.shape[0]
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    shape = (n,) + (1,) * (x.ndim - 2) + (-1,)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _attention(x, lw, *, cfg_key, fp8: bool):
    cfg = json.loads(cfg_key)
    eps, r, dn = cfg["norm_eps"], cfg["kv_lora_rank"], \
        cfg["qk_nope_head_dim"]
    inv, factor, scale = _yarn(cfg)
    inv = jnp.asarray(inv, jnp.float32)
    n = x.shape[0]
    a = _rms(x, lw["ln_attn.scale"], eps)
    q = _mm("sd,dhk->shk", a, lw["attn.wq"], fp8)
    kva = _mm("sd,dr->sr", a, lw["attn.wkv_a"], fp8)
    c = _rms(kva[:, :r], lw["attn.kv_norm.scale"], eps)
    kv = _mm("sr,rhk->shk", c, lw["attn.wkv_b"], fp8)
    q_pe = _rope_pairs(q[..., dn:], inv, factor)
    k_pe = _rope_pairs(kva[:, r:], inv, factor)
    s = _mm("qhd,thd->hqt", q[..., :dn], kv[..., :dn], fp8) + \
        _mm("qhd,td->hqt", q_pe, k_pe, fp8)
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
    o = _mm("hqt,thd->qhd", p, kv[..., dn:], fp8)
    return x + _mm("shk,hkd->sd", o, lw["attn.wo"], fp8)


def _swiglu(m, wg, wu, wd, fp8):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", m, wg, fp8))
               * _mm("sd,df->sf", m, wu, fp8), wd, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _dense_mlp(x, lw, *, cfg_key, fp8: bool):
    cfg = json.loads(cfg_key)
    m = _rms(x, lw["ln_mlp.scale"], cfg["norm_eps"])
    return x + _swiglu(m, lw["mlp.w_gate"], lw["mlp.w_up"],
                       lw["mlp.w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _experts(x, lw, *, cfg_key, fp8: bool):
    cfg = json.loads(cfg_key)
    m = _rms(x, lw["ln_mlp.scale"], cfg["norm_eps"])
    logits = _mm("sd,de->se", m, lw["moe.router"], fp8)
    if cfg["norm_topk_prob"]:
        top, idx = jax.lax.top_k(logits, cfg["top_k"])
        w = jax.nn.softmax(top, axis=-1)
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg["top_k"])
    # each token's weight for each expert (0 where not chosen)
    gate = jnp.zeros(logits.shape, jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)

    def one(y, e):
        return y + gate[:, e, None] * _swiglu(
            m, lw["moe.w_gate"][e], lw["moe.w_up"][e], lw["moe.w_down"][e],
            fp8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(logits.shape[1]))
    if "moe.shared_gate" in lw:
        y = y + _swiglu(m, lw["moe.shared_gate"], lw["moe.shared_up"],
                        lw["moe.shared_down"], fp8)
    return x + y


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _head(x, read_at, final_scale, head, *, cfg_key, fp8: bool):
    cfg = json.loads(cfg_key)
    return _mm("sd,dv->sv", _rms(x[read_at], final_scale, cfg["norm_eps"]),
               head, fp8)


def logits_at(w, cfg: dict, tokens: Sequence[int], read_at: Sequence[int],
              *, fp8: bool = False, bucket: int = 256) -> np.ndarray:
    """Logits (len(read_at), padded vocab) of the causal forward pass over
    ``tokens``, read at the given positions, one layer on the device in
    float32 at a time.  Sequences are padded at the end to a multiple of
    ``bucket`` (causal, and each token routed alone: pads never reach
    earlier positions)."""
    n = len(tokens)
    tok = np.zeros(-(-n // bucket) * bucket, np.int32)
    tok[:n] = tokens
    at = np.zeros(-(-len(read_at) // 64) * 64, np.int32)
    at[:len(read_at)] = read_at
    key = json.dumps({k: cfg[k] for k in KEYS + ("rope_theta",)
                      if k != "param_dtype"}, sort_keys=True)
    x = jnp.asarray(w["embed"])[jnp.asarray(tok)].astype(jnp.float32)
    for stack, mlp in (("dense_layers", _dense_mlp), ("layers", _experts)):
        names = [k for k in w if k.startswith(stack + "/")]
        if not names:
            continue
        for i in range(w[names[0]].shape[0]):
            lw = {k.split("/", 1)[1]: w[k][i] for k in names}
            x = _attention(x, lw, cfg_key=key, fp8=fp8)
            x = mlp(x, lw, cfg_key=key, fp8=fp8)
    head = w["embed"].T if cfg["tie_embeddings"] else w["lm_head"]
    out = _head(x, jnp.asarray(at), w["final_norm.scale"], head,
                cfg_key=key, fp8=fp8)
    return np.asarray(out, np.float32)[:len(read_at)]


# -- operations and bytes -----------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Dims:
    """Least operations (attention counted in the expanded form, the lesser:
    per head and key, the nope and rope dims of the score and the value
    dims) and least bytes (a floor: every weight outside the routed experts
    once a step, ``top_k`` routed experts per expert layer, and each token's
    latent and rope key per layer) of the family, from its sizes alone.  A
    multiply-add counts as two operations; the true vocabulary counts."""
    num_layers: int
    d_model: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    num_experts: int
    top_k: int
    num_shared_experts: int
    first_dense_layers: int
    dense_d_ff: int
    vocab_size: int
    compute_bytes: int = 2          # bf16 weights and latents as read

    @classmethod
    def of(cls, src) -> "Dims":
        get = src.get if isinstance(src, Mapping) else \
            (lambda k: getattr(src, k))
        return cls(**{f.name: int(get(f.name))
                      for f in dataclasses.fields(cls)
                      if f.name != "compute_bytes"})

    # -- parameters -----------------------------------------------------------
    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_dense_layers

    def attn_params(self) -> int:
        d, h, r = self.d_model, self.num_heads, self.kv_lora_rank
        dn, dr, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, \
            self.v_head_dim
        return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + \
            h * dv * d

    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    def shared_params(self) -> int:
        """Per expert layer: the router and the shared experts."""
        return self.d_model * self.num_experts + \
            self.num_shared_experts * self.expert_params()

    def dense_mlp_params(self) -> int:
        return 3 * self.d_model * self.dense_d_ff

    def head_params(self) -> int:
        return self.d_model * self.vocab_size

    # -- operations -----------------------------------------------------------
    def linear_flops(self) -> int:
        """Matmul operations of one token through every layer, no head:
        attention projections, the dense MLP, and per expert layer the
        router, ``top_k`` routed and the shared experts."""
        per = self.num_layers * self.attn_params() + \
            self.first_dense_layers * self.dense_mlp_params() + \
            self.moe_layers * (self.shared_params()
                               + self.top_k * self.expert_params())
        return 2 * per

    def attn_flops(self, ctx: int) -> int:
        return 2 * self.num_layers * self.num_heads * ctx * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)

    def head_flops(self) -> int:
        return 2 * self.head_params()

    def request_flops(self, prompt: int, served: int) -> Tuple[int, int]:
        """(prefill, decode) operations of one request, as the dense
        family's ``Dims.request_flops``."""
        pre = prompt * self.linear_flops() + \
            self.attn_flops(prompt * (prompt + 1) // 2) + self.head_flops()
        n_dec = max(0, served - 1)
        keys = n_dec * (prompt + 1) + n_dec * (n_dec - 1) // 2
        dec = n_dec * (self.linear_flops() + self.head_flops()) + \
            self.attn_flops(keys)
        return pre, dec

    # -- bytes ----------------------------------------------------------------
    def weight_bytes(self) -> int:
        """Every weight outside the routed experts, once: attention, the
        dense MLP, routers and shared experts, and the output head (the
        embedding gather reads a few rows only)."""
        return (self.num_layers * self.attn_params()
                + self.first_dense_layers * self.dense_mlp_params()
                + self.moe_layers * self.shared_params()
                + self.head_params()) * self.compute_bytes

    def expert_bytes(self) -> int:
        """One routed expert's weights."""
        return self.expert_params() * self.compute_bytes

    def kv_bytes_per_token(self) -> int:
        return self.num_layers * (self.kv_lora_rank + self.qk_rope_head_dim) \
            * self.compute_bytes

    def decode_bytes(self, steps: int, contexts: Iterable[int]) -> int:
        """The floor: per step the weights outside the routed experts and
        ``top_k`` experts a layer; each row's cached latents and its new
        one.  ``bench/metrics/decode_roofline.moe.py`` reads the experts
        the steps touched from the program's counters instead."""
        ctx = list(contexts)
        return steps * (self.weight_bytes() + self.moe_layers * self.top_k
                        * self.expert_bytes()) + \
            (sum(ctx) + len(ctx)) * self.kv_bytes_per_token()
