"""The plain reference against the engine's prefill and decode logits at the
smoke sizes, for OLMo-1B and for Yi-6B's family (grouped KV heads, RMSNorm,
untied head), in the dense layout and in the paged layout with pages shared
through the radix tree."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import counts, harness, reference

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# Yi-6B (01-ai/Yi-6B config.json) at 8 of its 32 layers: no cell serves it
# yet, but the reference keeps its family's paths
YI_6B = {"name": "yi-6b", "family": "dense", "num_layers": 8,
         "d_model": 4096, "num_heads": 32, "num_kv_heads": 4,
         "head_dim": 128, "d_ff": 11008, "vocab_size": 64000,
         "tie_embeddings": False, "norm_type": "rmsnorm", "norm_eps": 1e-5,
         "rope_theta": 5000000.0, "param_dtype": "float32",
         "compute_dtype": "bfloat16", "serve": {"arch": "yi-6b"}}


def config(arch):
    if arch == "yi-6b":
        return dict(YI_6B)
    with open(CONFIGS / f"{arch}.json") as f:
        return json.load(f)


PREFIX = "You are a precise data engine. Shared instruction text here. "


def served_logits(arch, layout, seed):
    """Serve five prompts with a shared prefix through the continuous
    batcher, recording the logits every served token was sampled from."""
    import repro.configs as C
    from repro.serving.engine import InferenceEngine
    from repro.serving.grammar import Field, JsonGrammar
    from repro.serving.scheduler import ContinuousBatcher, Request
    cfg = C.get_smoke_config(arch).replace(vocab_size=259)
    eng = InferenceEngine(cfg, seed=seed, max_len=256, kv_layout=layout,
                          page_size=16)
    rows = {}
    sample = eng._sample

    def tap(logits, gs, states, temperature):
        for b, g in enumerate(gs):
            if g is not None:
                rows.setdefault(id(g), []).append(np.array(logits[b]))
        return sample(logits, gs, states, temperature)

    eng._sample = tap
    reqs = [Request(prompt=PREFIX + f"row {i} says {'x' * 3 * i}",
                    grammar=JsonGrammar([Field("topic", "VARCHAR")],
                                        num_rows=1, max_str=6),
                    max_new_tokens=64) for i in range(5)]
    ContinuousBatcher(eng, num_slots=3).run(reqs, temperature=0.0)
    return eng, [(r, np.stack(rows[id(r.grammar)])) for r in reqs]


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-6b"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_engine_logits_match_reference(arch, layout):
    cfg = harness.reference_config(config(arch), smoke=True,
                                   family=harness.load_family(config(arch)))
    seed = 11
    eng, served = served_logits(arch, layout, seed)
    if layout == "paged":
        assert eng.total.radix_hit_tokens > 0      # shared pages were read
    w = reference.make_weights(cfg, seed)
    for req, got in served:
        ids = list(req.text.encode()) + [reference.EOS]
        assert len(got) == len(ids)
        prompt = [reference.BOS] + list(req.prompt.encode())
        at = [len(prompt) - 1 + k for k in range(len(ids))]
        want = reference.logits_at(w, cfg, prompt + ids[:-1], at)[:, :259]
        # the engine computes in bf16: about 2 % of the logits' scale at
        # these sizes; a wrong position, mask or cache entry moves them by
        # the scale itself
        scale = np.abs(want).max()
        assert np.abs(got[:, :259] - want).max() < 0.05 * scale


def test_reference_weights_are_the_programs():
    import jax
    import repro.configs as C
    from repro.models import model as MDL
    for arch in ("olmo-1b", "yi-6b"):
        cfg = harness.reference_config(
            config(arch), smoke=True, family=harness.load_family(config(arch)))
        pc = C.get_smoke_config(arch).replace(vocab_size=259)
        prog = MDL.init_params(pc, jax.random.PRNGKey(3))
        mine = reference.make_weights(cfg, 3)
        flat = {"/".join(str(getattr(k, "key", k)) for k in path): v
                for path, v in jax.tree_util.tree_flatten_with_path(prog)[0]}
        assert sorted(flat) == sorted(mine)
        for k in flat:
            # the same draws; one jitted call may round the scaling once
            # differently (one float32 ulp)
            np.testing.assert_allclose(np.asarray(flat[k]),
                                       np.asarray(mine[k]), rtol=3e-7,
                                       atol=0)


def test_grammar_choices():
    fields = [("topic", "VARCHAR")]
    ch = reference.choices(fields, 1, 3, '{"topic": "ab"}')
    # 'a' (no quote yet), 'b' (bytes or quote), closing quote (either)
    assert [i for i, _ in ch] == [11, 12, 13]
    assert 34 not in ch[0][1] and 34 in ch[1][1]
    assert reference.choices(fields, 1, 3, '{"topic": "abcd"}') is None
    assert reference.choices(fields, 1, 3, '{"topic": ""}') is None
    two = reference.choices([("ok", "BOOLEAN")], 2, 3,
                            '[{"ok": true}, {"ok": false}]')
    assert [a for _, a in two] == [(116, 102), (116, 102)]
    assert reference.choices([("ok", "BOOLEAN")], 2, 3,
                             '[{"ok": true}]') is None
    assert reference.widest_gap(np.array([[0.0, 2.0, 1.0]]), np.array([2]),
                                [(1, 2)]) == 1.0


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-6b"])
def test_dense_family_is_the_reference(arch):
    """The dense family the harness reaches through a configuration's
    ``family`` key gives the same weights, logits and counts, bit for bit,
    as ``bench/reference.py`` and ``bench/counts.py`` called directly."""
    if arch == "olmo-1b":
        plan = harness.cell_plan(harness.load_benchmark(),
                                 "olmo-1b.reviews-batch")
        family = plan["family"]
        assert family.Dims.of(plan["config"]) == \
            counts.Dims.of(config(arch))
    else:
        family = harness.load_family(config(arch))
    assert family.KEYS == counts.KEYS
    cfg = harness.reference_config(config(arch), smoke=True, family=family)
    mine, ref = family.make_weights(cfg, 7), reference.make_weights(cfg, 7)
    assert sorted(mine) == sorted(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(mine[k]), np.asarray(ref[k]))
    tokens = [reference.BOS] + list(b"a dense family reads these bytes")
    at = [3, 17, len(tokens) - 1]
    for fp8 in (False, True):
        np.testing.assert_array_equal(
            family.logits_at(mine, cfg, tokens, at, fp8=fp8),
            reference.logits_at(ref, cfg, tokens, at, fp8=fp8))
