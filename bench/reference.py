"""The grammar the served answers must follow, which every family shares,
and the plain float32 reference of the dense family only (named by
``bench/families/dense.py``).  Imports nothing of the program under test.

The weights are made from the seed by the same recipe the program states for
its random weights (one normal draw per leaf, leaves in sorted tree order,
scaled by 1/sqrt(fan_in); norm scales one), in one jitted call, in float32.
The forward pass follows the published descriptions: pre-norm blocks,
rotary embeddings (rotate-half), causal softmax attention with grouped
key/value heads, a SwiGLU MLP, a final norm and the output head.  Every
matmul runs at float32 ``highest`` precision, or, for the control, with both
operands rounded to float8 e4m3 (per-tensor scale) and float32 accumulation.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                       # largest finite float8_e4m3fn

# byte-level token ids: 0..255 are the bytes, then PAD, BOS, EOS
BOS, EOS = 257, 258
BYTE_IDS = 259                         # the ids a served token can take
STR_BYTES = tuple(b for b in range(32, 127) if b not in (34, 92))


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Every weight the family holds, stacked over layers, with the padded
    vocabulary the program allocates (its extra rows never score a byte)."""
    L, d, h, kv, hd, ff = (cfg[k] for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff"))
    if h % 16 and any(g * kv % 16 == 0 and g * kv <= math.ceil(h * 1.15)
                      for g in range(h // kv + 1, 2 * (h // kv))):
        raise ValueError("the program pads these query heads; the reference "
                         "does not")
    vp = padded_vocab(cfg["vocab_size"])
    out = {"embed": (vp, d),
           "layers/attn.wq": (L, d, h, hd), "layers/attn.wk": (L, d, kv, hd),
           "layers/attn.wv": (L, d, kv, hd), "layers/attn.wo": (L, h, hd, d),
           "layers/mlp.w_gate": (L, d, ff), "layers/mlp.w_up": (L, d, ff),
           "layers/mlp.w_down": (L, ff, d)}
    if cfg["norm_type"] == "rmsnorm":
        out["layers/ln_attn.scale"] = (L, d)
        out["layers/ln_mlp.scale"] = (L, d)
        out["final_norm.scale"] = (d,)
    if not cfg["tie_embeddings"]:
        out["lm_head"] = (d, vp)
    return out


def _tree_order(names: Sequence[str]) -> List[str]:
    """Sorted-key flattening order of the nested {top: {layer leaf}} tree."""
    top: Dict[str, List[str]] = {}
    for n in names:
        head, _, leaf = n.partition("/")
        top.setdefault(head, []).append(n)
    return [n for k in sorted(top) for n in sorted(top[k])]


def _fan_in(name: str, shape: Tuple[int, ...]) -> int:
    core = shape[1:] if name.startswith("layers/") else shape
    if name.endswith(("attn.wq", "attn.wk", "attn.wv")):
        return core[0]
    if name.endswith("attn.wo"):
        return core[0] * core[1]
    return core[-2]


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    shapes = leaf_shapes(cfg)
    order = _tree_order(list(shapes))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(order))
        out = {}
        for k, name in zip(keys, order):
            shp = shapes[name]
            if len(shp) - name.startswith("layers/") == 1:
                out[name] = jnp.ones(shp, jnp.float32)     # norm scales
            else:
                std = 1.0 / math.sqrt(max(1, _fan_in(name, shp)))
                out[name] = jax.random.normal(k, shp, jnp.float32) * std
        return out

    return build(jax.random.PRNGKey(seed))


# -- forward ---------------------------------------------------------------------
def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _norm(x, scale, kind: str, eps: float):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)                # non-parametric


def _rope(x, theta: float):
    n, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _logits(w, tokens, read_at, *, cfg_key, fp8: bool):
    cfg = dict(json.loads(cfg_key))
    kind, eps, theta = cfg["norm_type"], cfg["norm_eps"], cfg["rope_theta"]
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    n = tokens.shape[0]
    causal = jnp.tril(jnp.ones((n, n), bool))
    x = w["embed"][tokens]
    layer_keys = [k for k in w if k.startswith("layers/")]

    def block(x, lw):
        ones = jnp.ones((x.shape[-1],), jnp.float32)
        a = _norm(x, lw.get("layers/ln_attn.scale", ones), kind, eps)
        q = _rope(_mm("sd,dhk->shk", a, lw["layers/attn.wq"], fp8), theta)
        k = _rope(_mm("sd,dhk->shk", a, lw["layers/attn.wk"], fp8), theta)
        v = _mm("sd,dhk->shk", a, lw["layers/attn.wv"], fp8)
        q = q.reshape(n, kv, h // kv, hd)
        s = _mm("qkgd,tkd->kgqt", q, k, fp8) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("kgqt,tkd->qkgd", p, v, fp8).reshape(n, h, hd)
        x = x + _mm("shk,hkd->sd", o, lw["layers/attn.wo"], fp8)
        m = _norm(x, lw.get("layers/ln_mlp.scale", ones), kind, eps)
        g = _mm("sd,df->sf", m, lw["layers/mlp.w_gate"], fp8)
        u = _mm("sd,df->sf", m, lw["layers/mlp.w_up"], fp8)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u,
                    lw["layers/mlp.w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(block, x, {k: w[k] for k in layer_keys})
    x = _norm(x[read_at], w.get("final_norm.scale", 1.0), kind, eps)
    head = w["embed"].T if cfg["tie_embeddings"] else w["lm_head"]
    return _mm("sd,dv->sv", x, head, fp8)


def logits_at(w, cfg: dict, tokens: Sequence[int], read_at: Sequence[int],
              *, fp8: bool = False, bucket: int = 256) -> np.ndarray:
    """Logits (len(read_at), padded vocab) of the causal forward pass over
    ``tokens``, read at the given positions.  Sequences are padded at the
    end to a multiple of ``bucket`` (causal: pads never reach earlier
    positions) so that few programs compile."""
    n = len(tokens)
    padded = -(-n // bucket) * bucket
    tok = np.zeros(padded, np.int32)
    tok[:n] = tokens
    m = len(read_at)
    at = np.zeros(-(-m // 64) * 64, np.int32)
    at[:m] = read_at
    key = json.dumps({k: cfg[k] for k in (
        "norm_type", "norm_eps", "rope_theta", "num_heads", "num_kv_heads",
        "head_dim", "tie_embeddings")}, sort_keys=True)
    out = _logits(w, jnp.asarray(tok), jnp.asarray(at), cfg_key=key, fp8=fp8)
    return np.asarray(out, np.float32)[:m]


# -- the answer grammar ---------------------------------------------------------
def choices(fields: Sequence[Tuple[str, str]], num_rows: int, max_str: int,
            text: str) -> Optional[List[Tuple[int, Tuple[int, ...]]]]:
    """Walk a served answer (its bytes, then EOS) through the JSON the
    schema allows: ``{"name": value, ...}`` for one row, ``[{...}, ...]``
    for several; VARCHAR is 1..max_str printable bytes without quote or
    backslash, BOOLEAN is true or false.  Returns, for every served token
    that had more than one allowed choice, (its index, the allowed ids);
    None when the answer leaves the grammar."""
    ids = list(text.encode()) + [EOS]
    out: List[Tuple[int, Tuple[int, ...]]] = []
    i = 0

    def lit(s: str) -> bool:
        nonlocal i
        for b in s.encode():
            if i >= len(ids) or ids[i] != b:
                return False
            i += 1
        return True

    def value(typ: str) -> bool:
        nonlocal i
        t = typ.upper()
        if t == "VARCHAR":
            if not lit('"'):
                return False
            for j in range(max_str + 1):
                allowed = (STR_BYTES if j < max_str else ()) + \
                    ((34,) if j >= 1 else ())
                if i >= len(ids) or ids[i] not in allowed:
                    return False
                if len(allowed) > 1:
                    out.append((i, allowed))
                i += 1
                if ids[i - 1] == 34:
                    return True
            return False
        if t == "BOOLEAN":
            if i >= len(ids) or ids[i] not in (ord("t"), ord("f")):
                return False
            out.append((i, (ord("t"), ord("f"))))
            return lit("true" if ids[i] == ord("t") else "false")
        raise ValueError(f"the reference grammar has no type {typ}")

    def row() -> bool:
        if not lit("{"):
            return False
        for k, (name, typ) in enumerate(fields):
            if (k and not lit(", ")) or not lit(f'"{name}": ') or \
                    not value(typ):
                return False
        return lit("}")

    ok = row() if num_rows == 1 else (
        lit("[") and all((r == 0 or lit(", ")) and row()
                         for r in range(num_rows)) and lit("]"))
    if not ok or i != len(ids) - 1:
        return None
    return out


def widest_gap(ref: np.ndarray, picks: np.ndarray,
               allowed: Sequence[Tuple[int, ...]]) -> float:
    """Largest amount by which a picked token's reference logit lies below
    the reference's best allowed token, over the positions given."""
    worst = 0.0
    for row, pick, ok in zip(ref, picks, allowed):
        worst = max(worst, float(row[list(ok)].max() - row[pick]))
    return worst
