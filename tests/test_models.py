"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting output shapes + finite values; prefill/decode
consistency against the train-mode forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch import steps as ST
from repro.models import model as MDL
from repro.training import optim as OPT
from repro.models.config import ShapeSpec
from repro.training.data import DataConfig, synthetic_batch

ARCHS = C.ARCH_IDS


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = C.get_smoke_config(arch)
    B, S = 2, 32
    dcfg = DataConfig(batch=B, seq_len=S)
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_batch(cfg, dcfg, 0).items()}
    shape = ShapeSpec("smoke", seq_len=S, global_batch=B, kind="train")
    step_fn, _ = ST.make_train_step(
        cfg, None, shape, num_micro=1, donate=False,
        opt_cfg=OPT.AdamWConfig(warmup_steps=0))
    state = ST.init_train_state(cfg, jax.random.PRNGKey(0))

    params = state["params"]
    logits, _ = MDL.forward(cfg, params, batch, mode="train")
    exp_len = S if cfg.family != "vlm" else S  # vlm: prefix+text = S
    assert logits.shape[0] == B and logits.shape[1] == exp_len
    assert logits.shape[2] == cfg.padded_vocab
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: NaN in logits"

    new_state, metrics = step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"])), f"{arch}: NaN loss"
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    d0 = jax.tree.leaves(state["params"])[0]
    d1 = jax.tree.leaves(new_state["params"])[0]
    assert not np.allclose(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if C.get_config(a).supports_decode])
def test_prefill_decode_matches_forward(arch):
    cfg = C.get_smoke_config(arch)
    B, S = 2, 24
    key = jax.random.PRNGKey(1)
    params = MDL.init_params(cfg, key)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    batch = {"tokens": toks, "positions": pos}
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16)
    full, _ = MDL.forward(cfg, params, batch, mode="train")

    P = cfg.num_prefix_tokens if cfg.family == "vlm" else 0
    cache = MDL.init_cache(cfg, B, P + S + 8)
    pre_batch = dict(batch)
    pre_batch["tokens"] = toks[:, :S - 1]
    pre_batch["positions"] = pos[:, :S - 1]
    _, cache = MDL.forward(cfg, params, pre_batch, mode="prefill", cache=cache)
    # decode positions are absolute (prefix offset included for VLM)
    dec, _ = MDL.forward(cfg, params,
                         {"tokens": toks[:, S - 1:],
                          "positions": pos[:, S - 1:] + P},
                         mode="decode", cache=cache)
    err = float(jnp.max(jnp.abs(dec[:, 0] - full[:, -1])))
    assert err < 2e-2, f"{arch}: decode/train mismatch {err}"


def test_extend_prefill_matches_full():
    """Chunked prefill with cache extension == one-shot prefill."""
    cfg = C.get_smoke_config("yi-6b")
    B, P, S = 2, 16, 16
    key = jax.random.PRNGKey(2)
    params = MDL.init_params(cfg, key)
    toks = jax.random.randint(key, (B, P + S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(P + S, dtype=jnp.int32)[None], (B, P + S))

    full, _ = MDL.forward(cfg, params, {"tokens": toks, "positions": pos},
                          mode="train")

    cache = MDL.init_cache(cfg, B, P + S + 4)
    _, cache = MDL.forward(cfg, params,
                           {"tokens": toks[:, :P], "positions": pos[:, :P]},
                           mode="prefill", cache=cache)
    ext, cache = MDL.forward(cfg, params,
                             {"tokens": toks[:, P:], "positions": pos[:, P:]},
                             mode="prefill", cache=cache, extend_offset=P)
    err = float(jnp.max(jnp.abs(ext[:, -1] - full[:, -1])))
    assert err < 2e-2, f"extend mismatch {err}"


def test_param_counts_match_published():
    expected = {"mixtral-8x22b": 141e9, "qwen3-moe-30b-a3b": 30e9,
                "yi-6b": 6e9, "qwen2-7b": 7.6e9, "starcoder2-15b": 16e9,
                "falcon-mamba-7b": 7.3e9, "olmo-1b": 1.2e9,
                "paligemma-3b": 2.5e9, "hymba-1.5b": 1.6e9,
                "hubert-xlarge": 0.95e9}
    for arch, n in expected.items():
        got = C.get_config(arch).param_count()
        assert abs(got - n) / n < 0.08, f"{arch}: {got/1e9:.2f}B vs {n/1e9}B"


def test_actual_params_match_spec_tree():
    for arch in ARCHS:
        cfg = C.get_smoke_config(arch)
        params = MDL.init_params(cfg, jax.random.PRNGKey(0))
        assert MDL.param_count_actual(params) == cfg.param_count(padded=True)


def test_remat_policy_dots_trains():
    """§Perf opt D: the dots-saveable remat policy must train identically
    (same loss to fp tolerance) as full-recompute remat."""
    cfg = C.get_smoke_config("qwen2-7b")
    B, S = 2, 32
    from repro.training.data import DataConfig, synthetic_batch
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_batch(cfg, DataConfig(batch=B, seq_len=S), 0).items()}
    shape = ShapeSpec("smoke", seq_len=S, global_batch=B, kind="train")
    losses = []
    for pol in ("nothing", "dots"):
        step_fn, _ = ST.make_train_step(
            cfg, None, shape, donate=False, remat_policy=pol,
            opt_cfg=OPT.AdamWConfig(warmup_steps=0))
        state = ST.init_train_state(cfg, jax.random.PRNGKey(0))
        _, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert abs(losses[0] - losses[1]) < 1e-3, losses
