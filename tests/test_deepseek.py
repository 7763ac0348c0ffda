"""DeepSeek-V2-Lite's mechanisms in the program, at smoke size on the CPU:
YaRN rope at the published numbers, the `@<layers>` depth cut, the expert
block that drops no token, latent attention's absorbed decode against its
expanded prefill, the latent pool's layout, and the layouts it is refused
on.  (The engine against the family's float32 reference is in
bench/tests/test_bench_deepseek.py.)"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import layers as L
from repro.models import model as MDL
from repro.models import moe as MOE
from repro.serving.engine import InferenceEngine

ARCH = "deepseek-v2-lite@7"


def test_yarn_at_the_published_numbers():
    cfg = C.get_config(ARCH)
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    assert L.yarn_range(dim, base, cfg.yarn_original_max_pos,
                        cfg.yarn_beta_fast, cfg.yarn_beta_slow) == (10, 23)
    m = L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m == pytest.approx(1.26080, abs=1e-5)
    inv, factor, scale = MDL._rope_setup(cfg)
    assert factor == 1.0                 # mscale / mscale_all_dim
    assert scale == pytest.approx(192 ** -0.5 * m * m)
    assert scale == pytest.approx(0.1147214, abs=1e-6)
    i = np.arange(32)
    extra = base ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    # below `low` the period is kept, above `high` it is 40 times longer
    np.testing.assert_allclose(np.asarray(inv)[:10], extra[:10], rtol=2e-6)
    np.testing.assert_allclose(np.asarray(inv)[23:], extra[23:] / 40,
                               rtol=2e-6)


def test_plain_rope_is_unchanged_by_the_frequency_argument():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(5)[None], (2, 5))
    np.testing.assert_array_equal(
        np.asarray(L.rope(x, pos, 1e4)),
        np.asarray(L.rope(x, pos, 1e4, L.rope_inv_freq(16, 1e4))))


def test_depth_cut_resolves_and_refuses():
    full = C.get_config("deepseek-v2-lite")
    cut = C.get_config(ARCH)
    assert (full.num_layers, cut.num_layers) == (27, 7)
    assert cut.replace(num_layers=27, name=full.name) == full
    assert cut.num_moe_layers == 6 and cut.first_dense_layers == 1
    # 81.0 M dense layer + 6 x 584.8 M expert layers + the embedding and
    # the head: 4.01 B parameters
    assert cut.param_count() == pytest.approx(4.01e9, rel=1e-3)
    assert C.get_smoke_config(ARCH).num_layers == 3      # all it has
    assert C.get_smoke_config("deepseek-v2-lite@2").num_layers == 2
    assert C.get_config("olmo-1b@4").num_layers == 4
    for bad in ("deepseek-v2-lite@0", "deepseek-v2-lite@1",
                "deepseek-v2-lite@28", "deepseek-v2-lite@x",
                "deepseek-v2-lite@", "deepseek-v3@7"):
        with pytest.raises(KeyError):
            C.get_config(bad)


def _moe_params(E, M, F, shared, key):
    ks = jax.random.split(key, 7)
    p = {"router": jax.random.normal(ks[0], (M, E)),
         "w_gate": jax.random.normal(ks[1], (E, M, F)) / math.sqrt(M),
         "w_up": jax.random.normal(ks[2], (E, M, F)) / math.sqrt(M),
         "w_down": jax.random.normal(ks[3], (E, F, M)) / math.sqrt(F)}
    if shared:
        p.update(shared_gate=jax.random.normal(ks[4], (M, 2 * F)),
                 shared_up=jax.random.normal(ks[5], (M, 2 * F)),
                 shared_down=jax.random.normal(ks[6], (2 * F, M)))
    return p


@pytest.mark.parametrize("norm_topk_prob,shared", [(True, False),
                                                   (False, True)])
def test_no_expert_choice_is_dropped(norm_topk_prob, shared):
    """Every token routed to one expert: the capacity rule of 1.25 tokens
    per expert slot would have dropped most of them.  The grouped path
    still equals the dense loop over all experts."""
    E, K, M, F, T = 8, 2, 32, 16, 64
    p = _moe_params(E, M, F, shared, jax.random.PRNGKey(1))
    p["router"] = p["router"].at[:, 3].add(50.0)     # expert 3 for all
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (T, M)))
    y, idx = MOE.moe_block(x, p, num_experts=E, top_k=K,
                           norm_topk_prob=norm_topk_prob,
                           compute_dtype=jnp.float32)
    assert int((idx == 3).sum()) == T > math.ceil(1.25 * T * K / E)
    want = MOE.moe_block_reference(x, p, num_experts=E, top_k=K,
                                   norm_topk_prob=norm_topk_prob)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    valid = jnp.arange(T) < T // 2
    assert int(MOE.experts_touched(idx, E, valid)) == \
        len(set(np.asarray(idx[:T // 2]).ravel().tolist()))


def test_routing_rules():
    logits = jnp.log(jnp.array([[0.1, 0.5, 0.15, 0.25]]))
    g, i = MOE.route(jnp.ones((1, 1)), logits, 2, norm_topk_prob=True)
    np.testing.assert_allclose(np.asarray(g), [[0.5 / 0.75, 0.25 / 0.75]],
                               rtol=1e-6)
    g, i = MOE.route(jnp.ones((1, 1)), logits, 2, norm_topk_prob=False)
    np.testing.assert_allclose(np.asarray(g), [[0.5, 0.25]], rtol=1e-6)
    assert np.asarray(i).tolist() == [[1, 3]]


def _smoke():
    return C.get_smoke_config(ARCH).replace(vocab_size=259)


def test_absorbed_decode_matches_expanded_prefill():
    """Prefill n tokens into the latent pool, decode token n + 1 with the
    absorbed query, against a prefill of all n + 1 tokens (expanded keys
    and values).  float32 throughout: only summation order differs."""
    cfg = _smoke()
    params = MDL.init_params(cfg, jax.random.PRNGKey(4))
    B, S, ps = 2, 21, 4
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, 259)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    bt = jnp.arange(B * 8, dtype=jnp.int32).reshape(B, 8)[:, ::-1]
    pool = MDL.init_paged_cache(cfg, B * 8, ps)

    def prefill(n):
        cache = dict(pool, block_tables=bt,
                     prefix_table=jnp.zeros((0,), jnp.int32),
                     prefix_len=jnp.int32(0))
        return MDL.forward(cfg, params, {"tokens": toks[:, :n],
                                         "positions": pos[:, :n]},
                           mode="prefill", cache=cache, last_only=True)

    full, _ = prefill(S)
    _, cache = prefill(S - 1)
    dec, new = MDL.forward(cfg, params, {"tokens": toks[:, S - 1:],
                                         "positions": pos[:, S - 1:]},
                           mode="decode",
                           cache={"ckv": cache["ckv"], "idx": cache["idx"],
                                  "block_tables": bt})
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]),
                               rtol=1e-4, atol=1e-4)
    assert new["expert_loads"].shape == (cfg.num_moe_layers,)
    # the decode wrote the new token's latent where the prefill would
    np.testing.assert_allclose(np.asarray(new["ckv"]),
                               np.asarray(prefill(S)[1]["ckv"]),
                               rtol=1e-5, atol=1e-5)


def test_latent_pool_layout():
    cfg = C.get_config(ARCH)
    specs = MDL.paged_cache_specs(cfg, 1024, 64, 32)
    assert set(specs) == {"idx", "ckv"}
    # 512 latent + 64 rope key values padded to 5 x 128 lanes
    assert specs["ckv"].shape == (7, 1, 1024, 64, 640)
    eng = InferenceEngine(cfg, params=MDL.param_specs(cfg), max_len=2048,
                          kv_layout="paged", page_size=64)
    assert eng.pool_keys == ("ckv",)
    assert eng._page_bytes() == 7 * 64 * 576 * 2


@pytest.mark.parametrize("opts", [{"kv_layout": "dense"},
                                  {"kv_layout": "paged", "kv_quant": "int8"},
                                  {"kv_layout": "paged",
                                   "prefix_cache_mode": "exact"}])
def test_latent_attention_is_refused_off_its_layout(opts):
    cfg = _smoke()
    with pytest.raises(ValueError, match="paged layout"):
        InferenceEngine(cfg, params=MDL.param_specs(cfg), **opts)


@pytest.mark.parametrize("norm_type", ["rmsnorm", "layernorm"])
def test_norm_eps_is_the_configs(norm_type):
    """The default eps is the norm's old constant; another eps changes
    the output."""
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 8)) * 1e-2
    p = {"scale": jnp.ones(8), "bias": jnp.zeros(8)}
    cfg = C.get_smoke_config("olmo-1b").replace(norm_type=norm_type)
    old = L.rmsnorm(x, p["scale"]) if norm_type == "rmsnorm" else \
        L.layernorm(x, p["scale"], p["bias"])
    np.testing.assert_array_equal(
        np.asarray(L.apply_norm(norm_type, x, p, cfg.eps)), np.asarray(old))
    other = L.apply_norm(norm_type, x, p, 1e-3)
    assert np.abs(np.asarray(other) - np.asarray(old)).max() > 1e-2
    assert C.get_config("yi-6b").eps == 1e-5
    assert C.get_config("deepseek-v2-lite").eps == 1e-6
