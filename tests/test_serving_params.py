"""The serving tree: the weights the engine's step programs take, with every
leaf the forward only casts held in the compute dtype.  Its leaves are in the
compute dtype exactly where the forward reads them so, and the step programs
give the same logits and caches from it as from the float32 tree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as MDL
from repro.serving.engine import InferenceEngine

# dense (non-parametric LN, tied head), dense (rmsnorm, qkv bias, own head),
# moe (float32 router), hybrid (ssm)
ARCHS = ["olmo-1b", "qwen2-7b", "mixtral-8x22b", "hymba-1.5b"]
# leaves the forward reads in float32
FLOAT32_READ = ("moe.router", "ssm.A_log", "ssm.D", "ssm.dt_bias")


def _float32_read(name: str) -> bool:
    return name in FLOAT32_READ or name.endswith((".scale", ".bias"))


def _named_leaves(tree):
    return {jax.tree_util.keystr(p): (p[-1].key, x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _params(cfg, seed=7):
    """Seeded weights with every leaf off the bf16 grid, so that a float32
    leaf cast by mistake changes the logits."""
    params = MDL.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return treedef.unflatten(
        [x + 0.01 * jax.random.normal(k, x.shape, x.dtype)
         for k, x in zip(keys, leaves)])


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_tree_casts_exactly_the_compute_leaves(arch):
    cfg = C.get_smoke_config(arch)
    assert (cfg.param_dtype, cfg.compute_dtype) == ("float32", "bfloat16")
    params = _params(cfg)
    served = _named_leaves(MDL.serving_params(cfg, params))
    names = set()
    for path, (name, x) in _named_leaves(params).items():
        names.add(name)
        got = served[path][1]
        if _float32_read(name):
            assert got is x, path
        else:
            assert got.dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(x.astype(jnp.bfloat16)))
    # the leaves each family brings are among those checked
    want = {"olmo-1b": {"embed", "attn.wq", "attn.wo", "mlp.w_gate",
                        "mlp.w_down"},
            "qwen2-7b": {"lm_head", "attn.bq", "ln_attn.scale",
                         "final_norm.scale"},
            "mixtral-8x22b": {"moe.router", "moe.w_up", "ln_mlp.scale"},
            "hymba-1.5b": {"ssm.A_log", "ssm.D", "ssm.dt_bias", "ssm.in_x",
                           "ssm.out_proj"}}[arch]
    assert want <= names, want - names


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_tree_maps_specs_to_specs(arch):
    cfg = C.get_smoke_config(arch)
    specs = MDL.serving_params(cfg, MDL.param_specs(cfg))
    arrays = MDL.serving_params(cfg, MDL.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    assert all(isinstance(s, jax.ShapeDtypeStruct)
               for s in jax.tree.leaves(specs))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), specs) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), arrays)
    assert MDL.tree_bytes(specs) == sum(x.nbytes
                                        for x in jax.tree.leaves(arrays))


def test_same_dtypes_return_the_tree_given():
    cfg = C.get_smoke_config("olmo-1b").replace(compute_dtype="float32")
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    assert MDL.serving_params(cfg, params) is params
    eng = InferenceEngine(cfg, params=params, max_len=64)
    assert eng.params is params
    assert eng.param_bytes == sum(x.nbytes for x in jax.tree.leaves(params))


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_builds_its_weights_as_the_serving_tree(arch):
    """Seeded weights drawn leaf by leaf into the serving tree equal the
    float32 tree cast afterwards; the engine holds that one tree."""
    cfg = C.get_smoke_config(arch)
    eng = InferenceEngine(cfg, seed=3, max_len=64)
    want = MDL.serving_params(cfg, MDL.init_params(cfg,
                                                   jax.random.PRNGKey(3)))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), eng.params, want)
    assert jax.tree.map(lambda x: x.dtype, eng.params) == \
        jax.tree.map(lambda x: x.dtype, want)
    assert eng.param_bytes == MDL.tree_bytes(want) < MDL.tree_bytes(
        MDL.param_specs(cfg))


def _same(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x, np.float32), np.asarray(y, np.float32)), a, b)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


@pytest.mark.parametrize("arch", ARCHS)
def test_dense_steps_match_the_float32_tree(arch):
    """The engine's prefill and dense decode programs, given the serving
    tree, give the logits and caches they give from the float32 tree."""
    cfg = C.get_smoke_config(arch)
    params = _params(cfg)
    eng = InferenceEngine(cfg, params=params, max_len=64)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = MDL.init_cache(cfg, B, eng.max_len)
    cache["row_idx"] = jnp.zeros((B,), jnp.int32)
    prefill = eng._prefill_fn(B, S, 0)
    lg_s, c_s = prefill(eng.params, toks, pos, _copy(cache))
    lg_f, c_f = prefill(params, toks, pos, _copy(cache))
    _same(lg_s, lg_f)
    _same(c_s, c_f)

    c_f = dict(c_f, row_idx=jnp.full((B,), S, jnp.int32))
    decode = eng._decode_fn()
    tok = toks[:, -1:]
    dpos = jnp.full((B, 1), S, jnp.int32)
    for _ in range(2):
        lg_s, c_s = decode(eng.params, tok, dpos, _copy(c_f))
        lg_f, c_f = decode(params, tok, dpos, c_f)
        _same(lg_s, lg_f)
        _same(c_s, c_f)
        tok, dpos = jnp.argmax(lg_f, -1)[:, None].astype(jnp.int32), dpos + 1


def _layer_by_layer_decode(cfg, params, tok, pos, cache, bt):
    """The paged decode step as a loop over layers, each handed its own
    slice of the pool and returning its new slice, stacked back after the
    last layer: the formulation the scan had before the pool rode in its
    carry."""
    cd = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"].astype(cd), tok, axis=0)
    keys = [k for k in ("k", "v", "conv", "h") if k in cache]
    new = {k: [] for k in keys}
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        cl = {k: cache[k][i:i + 1] for k in keys}
        x, cl, _ = MDL._block(cfg, x, lp, pos, "decode",
                              {**cl, "idx": cache["idx"]}, 0,
                              paged={"block_tables": bt})
        for k in keys:
            new[k].append(cl[k][0])
    x = MDL.L.apply_norm(cfg.norm_type, x, MDL._norm_p(params, "final_norm"),
                         cfg.eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head.astype(cd))[:, 0], {k: jnp.stack(v)
                                         for k, v in new.items()}


# the served architecture again, with two of three rows' writes dropped: one
# row's table entry for its position is -1, another's position lies past its
# table
@pytest.mark.parametrize("arch", ARCHS + ["olmo-1b-dropped"])
def test_paged_decode_matches_the_float32_tree(arch):
    dropped = arch.endswith("-dropped")
    cfg = C.get_smoke_config(arch.removesuffix("-dropped"))
    if dropped:
        # float32 compute: the loop below then gives the step's logits to
        # the last bit, with no bf16 rounding that differs between programs
        cfg = cfg.replace(compute_dtype="float32")
    params = _params(cfg)
    eng = InferenceEngine(cfg, params=params, max_len=64, kv_layout="paged",
                          page_size=16)
    B, P, PS, NB = 2, 8, 16, 4
    if dropped:
        B, P = 3, 12
    cache = MDL.init_paged_cache(cfg, P, PS, B)
    keys = iter(jax.random.split(jax.random.PRNGKey(2), len(cache)))
    cache = {k: (v if k == "idx" else
                 jax.random.normal(next(keys), v.shape).astype(v.dtype))
             for k, v in cache.items()}
    bt = jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    tok = jnp.array([[5], [9], [2]][:B], jnp.int32)
    pos = jnp.array([[20], [37], [70]][:B], jnp.int32)
    if dropped:
        bt = bt.at[1, 37 // PS].set(-1)
    decode = eng._decode_fn_paged(NB)
    lg_s, c_s = decode(eng.params, tok, pos, _copy(cache), bt, None)
    lg_f, c_f = decode(params, tok, pos, _copy(cache), bt, None)
    _same(lg_s, lg_f)
    _same(c_s, c_f)
    if dropped:
        want, pools = jax.jit(_layer_by_layer_decode, static_argnums=0)(
            cfg, params, tok, pos, cache, bt)
        _same(lg_f, want)
        _same({k: c_f[k] for k in pools}, pools)
        # row 0 wrote its slot; the other rows wrote nothing anywhere
        page, off = int(bt[0, 20 // PS]), 20 % PS
        for k in ("k", "v"):
            moved = c_f[k] != cache[k]
            assert bool(moved[:, :, page, off].any())
            assert not bool(moved.at[:, :, page, off].set(False).any())
