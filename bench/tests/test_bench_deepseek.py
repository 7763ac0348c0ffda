"""The DeepSeek-V2 family (bench/families/deepseek_v2.py) against the
program at the smoke sizes on the CPU: the same weights, the engine's
paged prefill and its decode through the latent pool against the float32
reference's full forward pass, with and without a prefix shared through
the radix tree; and the family's counts at full size against hand counts."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, reference
from bench.tests import test_bench_faults

ROOT = Path(__file__).resolve().parents[2]
CELL = "deepseek-v2-lite.docs-wide"


def plan():
    return harness.cell_plan(harness.load_benchmark(ROOT), CELL)


def smoke_config(p):
    return harness.reference_config(p["config"], smoke=True,
                                    family=p["family"])


def test_the_program_is_the_files_configuration():
    p = plan()
    harness.check_program_config(p["config"], p["family"])
    bad = dict(p["config"], yarn_factor=32.0)
    with pytest.raises(ValueError, match="yarn_factor"):
        harness.check_program_config(bad, p["family"])


def test_reference_weights_are_the_programs():
    import jax
    import repro.configs as C
    from repro.models import model as MDL
    p = plan()
    cfg = smoke_config(p)
    prog = MDL.init_params(C.get_smoke_config("deepseek-v2-lite@7").replace(
        vocab_size=259), jax.random.PRNGKey(3))
    mine = p["family"].make_weights(cfg, 3)
    flat = {"/".join(k.key for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(prog)[0]}
    assert sorted(flat) == sorted(mine)
    for k in flat:                     # drawn by the same calls: bit equal
        assert flat[k].dtype == mine[k].dtype, k
        np.testing.assert_array_equal(np.asarray(flat[k]),
                                      np.asarray(mine[k]), err_msg=k)


PREFIX = "You are a precise data engine. Shared instruction text here. "


@pytest.mark.parametrize("shared", [False, True])
def test_engine_logits_match_reference(shared):
    """Five prompts through the continuous batcher on the paged layout:
    each slot's prefill, then its decode ticks through the latent pool.
    With ``shared``, the prompts open with a common instruction whose full
    pages the radix tree hands to every later fill, read in place."""
    import repro.configs as C
    from repro.serving.engine import InferenceEngine
    from repro.serving.grammar import Field, JsonGrammar
    from repro.serving.scheduler import ContinuousBatcher, Request
    p = plan()
    cfg, family, seed = smoke_config(p), p["family"], 13
    eng = InferenceEngine(
        C.get_smoke_config("deepseek-v2-lite@7").replace(vocab_size=259),
        seed=seed, max_len=256, kv_layout="paged", page_size=16)
    rows, sample = {}, eng._sample

    def tap(logits, gs, states, temperature):
        for b, g in enumerate(gs):
            if g is not None:
                rows.setdefault(id(g), []).append(np.array(logits[b]))
        return sample(logits, gs, states, temperature)

    eng._sample = tap
    lead = PREFIX if shared else ""
    reqs = [Request(prompt=lead + f"row {i} says {'xy' * (3 + 2 * i)}",
                    grammar=JsonGrammar([Field("topic", "VARCHAR")],
                                        num_rows=1, max_str=6),
                    max_new_tokens=64) for i in range(5)]
    batcher = ContinuousBatcher(eng, num_slots=3)
    batcher.run(reqs, temperature=0.0)
    st = batcher.stats
    assert (st.radix_hit_tokens > 0) == shared
    # each decode launch counted its 2 expert layers x 8 experts (a tick
    # whose rows all finished launches none), each load at most all
    launches, left = divmod(st.expert_slots_decode, 2 * 8)
    assert left == 0 and 0 < launches <= st.decode_steps
    assert 0 < st.expert_loads_decode <= st.expert_slots_decode
    assert st.expert_slots_prefill == 5 * 2 * 8
    w = family.make_weights(cfg, seed)
    for req in reqs:
        got = np.stack(rows[id(req.grammar)])
        ids = list(req.text.encode()) + [reference.EOS]
        assert len(got) == len(ids) > 1
        prompt = [reference.BOS] + list(req.prompt.encode())
        at = [len(prompt) - 1 + k for k in range(len(ids))]
        want = family.logits_at(w, cfg, prompt + ids[:-1], at)[:, :259]
        # the smoke variant computes in float32: the program and the
        # reference differ by summation order (about 1e-6 of the logits'
        # scale); a wrong position, page, rope pair, latent or routing
        # moves them by the scale itself
        scale = np.abs(want).max()
        assert np.abs(got[:, :259] - want).max() < 1e-3 * scale


def test_counts_at_full_size():
    """Dims from the configuration file against the counts by hand."""
    d = plan()["family"].Dims.of(
        json.loads((ROOT / "bench/configs/deepseek-v2-lite.json").read_text()))
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert d.attn_params() == attn == 13_762_560
    expert = 3 * 2048 * 1408
    assert d.expert_bytes() == 2 * expert == 17_301_504   # 17.3 MB
    dense_mlp = 3 * 2048 * 10944
    per_moe = 2048 * 64 + 2 * expert                      # router + shared
    head = 2048 * 102400
    assert d.weight_bytes() == 2 * (7 * attn + dense_mlp + 6 * per_moe
                                    + head)
    assert d.linear_flops() == 2 * (7 * attn + dense_mlp
                                    + 6 * (per_moe + 6 * expert))
    assert d.attn_flops(10) == 2 * 7 * 16 * 10 * (128 + 64 + 128)
    assert d.kv_bytes_per_token() == 7 * 576 * 2
    assert d.decode_bytes(3, [5, 6]) == \
        3 * (d.weight_bytes() + 6 * 6 * 2 * expert) + 13 * 7 * 576 * 2
    pre, dec = d.request_flops(4, 3)
    assert pre == 4 * d.linear_flops() + d.attn_flops(10) + 2 * head
    assert dec == 2 * (d.linear_flops() + 2 * head) + d.attn_flops(5 + 6)


READERS = ("experts_touched_share", "decode_roofline.moe",
           "prefill_roofline.moe", "mfu.moe", "slot_occupancy.moe",
           "hbm_peak_share.moe")


def _ctx(stats):
    """A traced closed-loop window: two 8-token prompts, 4 served bytes
    each, in one query; 5 decode launches and 2 prefills on the device."""
    from bench import peaks
    p = plan()
    reqs = [{"prompt": [1] * 8, "text": "abc"} for _ in range(2)]
    return {"loop": "closed", "requests": reqs, "radix_hit_tokens": 4,
            "queries": [{"stats": stats}], "memory_peak_bytes": 2 ** 33,
            "window": {"start": 0.0, "end": 2.0},
            "dims": p["family"].Dims.of(p["config"]),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "trace": {"program_s": {"jit_paged_decode_step": 0.5,
                                    "jit_paged_prefill_step": 0.25},
                      "program_n": {"jit_paged_decode_step": 5,
                                    "jit_paged_prefill_step": 2}}}


def test_moe_readers():
    stats = {"decode_rows": 10, "decode_slots": 16,
             "expert_loads_decode": 300, "expert_slots_decode": 384,
             "expert_loads_prefill": 200, "expert_slots_prefill": 768}
    ctx = _ctx(stats)
    read = {m: harness.load_reader(m)(ctx) for m in READERS}
    assert read["experts_touched_share"] == 100 * 300 / 384
    assert read["slot_occupancy.moe"] == 100 * 10 / 16
    assert read["hbm_peak_share.moe"] == 100 * 2 ** 33 / 2 ** 34
    d, pk = ctx["dims"], ctx["peaks"]
    # decode: 5 steps of the weights outside the routed experts, each
    # expert load once, the rows' latents (contexts 9, 10, 11 per row)
    dec_bytes = 5 * d.weight_bytes() + 300 * d.expert_bytes() + \
        2 * (9 + 10 + 11 + 3) * d.kv_bytes_per_token()
    dec_flops = 2 * d.request_flops(8, 4)[1]
    assert read["decode_roofline.moe"] == pytest.approx(
        100 * max(dec_flops / pk["bf16_flops"],
                  dec_bytes / pk["hbm_bytes_per_s"]) / 0.5)
    pre_bytes = 2 * d.weight_bytes() + 200 * d.expert_bytes() + \
        12 * d.kv_bytes_per_token()
    pre_flops = 2 * d.request_flops(8, 1)[0] - 4 * d.linear_flops()
    assert read["prefill_roofline.moe"] == pytest.approx(
        100 * max(pre_flops / pk["bf16_flops"],
                  pre_bytes / pk["hbm_bytes_per_s"]) / 0.25)
    flops = sum(sum(d.request_flops(8, 4)) for _ in range(2)) - \
        4 * d.linear_flops()
    assert read["mfu.moe"] == pytest.approx(
        100 * flops / (2.0 * pk["bf16_flops"]))
    # a program without the expert counters (the parent) reads nothing
    # from the counter-based readers, and raises nothing
    bare = _ctx({"decode_rows": 10, "decode_slots": 16})
    for m in ("experts_touched_share", "decode_roofline.moe",
              "prefill_roofline.moe"):
        assert harness.load_reader(m)(bare) is None


@pytest.mark.parametrize("fault", sorted(test_bench_faults.FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    """The faults of bench/tests/test_bench_faults.py, in this cell: the
    latent pool left unchanged by a decode step, half a dispatch's batch
    unanswered, a token altered where it is produced."""
    plan = test_bench_faults.small_plan(CELL)
    test_bench_faults.FAULTS[fault](monkeypatch)
    out = test_bench_faults.run(plan)
    assert out["correct"] is False, out["checks"]


def test_control_is_not_correct():
    """The float8 control of bench/tests/test_bench_control.py in this cell:
    not correct under the cell's limits, while the program is within them."""
    plan = test_bench_faults.small_plan(CELL)
    lim = plan["limits"]
    out = test_bench_faults.run(plan, seed=2 ** 31 + 9, control=True)
    c = out["control"]
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_err"]["value"] == c["control_err"]
    assert c["err"] <= lim["max_logit_err"], c
    assert c["gap"] <= lim["max_logit_gap"], c
