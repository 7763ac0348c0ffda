"""Configurations, model families, traffic mixes and metrics are files
found by name: one placed beside the others is taken up without editing any
existing file; the generator gives every seed the same work."""
import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import harness, traffic
from bench.tests.test_bench_faults import run, shrink

ROOT = Path(__file__).resolve().parents[2]

# a family that is the dense one under another name, noting each use
TOY_FAMILY = '''from bench import counts, reference

CALLS = []


class _Keys(tuple):
    def __iter__(self):
        CALLS.append("KEYS")
        return super().__iter__()


KEYS = _Keys(counts.KEYS)


def make_weights(cfg, seed):
    CALLS.append("make_weights")
    return reference.make_weights(cfg, seed)


def logits_at(w, cfg, tokens, read_at, *, fp8=False):
    CALLS.append("logits_at")
    return reference.logits_at(w, cfg, tokens, read_at, fp8=fp8)


class Dims(counts.Dims):
    @classmethod
    def of(cls, src):
        CALLS.append("Dims.of")
        return super().of(src)
'''


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_taken_up(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "bench")
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "olmo-1b.json").read_text())
    cfg.update(name="tiny-model", family="toy")
    (b / "configs" / "tiny-model.json").write_text(json.dumps(cfg))
    (b / "families" / "toy.py").write_text(TOY_FAMILY)
    mix = json.loads((b / "traffic" / "reviews-batch.json").read_text())
    mix["rows_per_query"] = 8
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "tiny_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (b / "limits" / "tiny-model.tiny-mix.json").write_text(
        '{"max_logit_err": 0.05, "max_logit_gap": 0.03}')
    spec = harness.load_benchmark(ROOT)
    spec["configs"].append({"name": "tiny-model", "source": "x",
                            "file": "bench/configs/tiny-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny-model.tiny-mix",
                              "config": "tiny-model", "traffic": "tiny-mix",
                              "chips": 1, "why": "x"})
    spec["end_to_end"][1]["workloads"].append("tiny-model.tiny-mix")
    spec["per_layer"].append({"name": "tiny_metric", "unit": "x",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "rows_per_s",
                              "workloads": ["tiny-model.tiny-mix"]})
    plan = harness.cell_plan(spec, "tiny-model.tiny-mix", root=tmp_path)
    assert plan["config"]["name"] == "tiny-model"
    assert plan["mix"]["rows_per_query"] == 8
    assert plan["limits"] == {"max_logit_err": 0.05, "max_logit_gap": 0.03}
    assert plan["readers"]["tiny_metric"]({}) == 42.0
    assert [m["name"] for m in plan["end_to_end"]] == ["setup_s",
                                                       "rows_per_s"]
    assert "calls_per_dispatch" not in plan["readers"]
    toy = plan["family"]
    assert Path(toy.__file__) == b / "families" / "toy.py"
    assert Path(harness.cell_plan(spec, "olmo-1b.reviews-batch",
                                  root=tmp_path)["family"].__file__) == \
        b / "families" / "dense.py"
    harness.check_program_config(plan["config"], toy)
    assert toy.CALLS == ["KEYS"]
    # the reference, its sizes and the counts the metrics read come from
    # the toy module; it is the dense family, so the run is correct
    out = run(shrink(plan))
    assert out["correct"] is True, out["checks"]
    assert {"KEYS", "make_weights", "logits_at", "Dims.of"} <= \
        set(toy.CALLS[1:])
    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("family", [None, "moe"])
def test_a_configuration_names_its_family(tmp_path, family):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "bench" / "configs" / "olmo-1b.json"
    cfg = json.loads(path.read_text())
    cfg.pop("family")
    if family:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    spec = harness.load_benchmark(ROOT)
    # there is no default family: the error names the key and the path
    with pytest.raises((ValueError, FileNotFoundError)) as e:
        harness.cell_plan(spec, "olmo-1b.reviews-batch", root=tmp_path)
    msg = str(e.value)
    assert "'family'" in msg
    assert ("bench/families/moe.py" if family else
            "bench/configs/olmo-1b.json") in msg


def test_every_cell_finds_its_files():
    spec = harness.load_benchmark(ROOT)
    for wl in spec["workloads"]:
        plan = harness.cell_plan(spec, wl["name"])
        assert plan["end_to_end"][0]["name"] == "setup_s"
        assert len(plan["end_to_end"]) == 2 and plan["per_layer"]
        assert plan["limits"]["max_logit_gap"] > 0


def test_seeds_order_the_same_work():
    mix = traffic.load("reviews-batch")
    a = traffic.closed_loop_rows(mix, 1, 3, set())
    b = traffic.closed_loop_rows(mix, 2 ** 31 + 5, 3, set())
    assert a == traffic.closed_loop_rows(mix, 1, 3, set())
    assert a != b
    per = mix["rows_per_query"]
    for k in range(3):
        la = sorted(len(r["review"]) for r in a[k * per:(k + 1) * per])
        lb = sorted(len(r["review"]) for r in b[k * per:(k + 1) * per])
        assert la == lb
    look = traffic.load("lookup-open-loop")
    sa = traffic.open_loop_schedule(look, 3, 20.0, set())
    sb = traffic.open_loop_schedule(look, 4, 20.0, set())
    # the open loop replays the mix's own arrival trace under every seed
    assert len(sa["due"]) == int(look["open"]["rate_qps"] * 20)
    assert np.array_equal(sa["due"], sb["due"])
    assert [len(sa["rows"][i]["review"]) for i in sa["ids"]] == \
        [len(sb["rows"][i]["review"]) for i in sb["ids"]]
    assert sa["ids"].tolist() != sb["ids"].tolist()
    assert sorted(np.bincount(sa["tenants"])) == sorted(np.bincount(sb["tenants"]))
    assert len(set(sa["ids"].tolist())) == len(sa["ids"])


def test_a_mix_names_another_mixs_table(tmp_path):
    shutil.copytree(ROOT / "bench" / "traffic", tmp_path / "traffic")
    mix = json.loads((tmp_path / "traffic" / "reviews-batch.json").read_text())
    mix["table"] = "lookup-open-loop"
    (tmp_path / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    reviews = traffic.load("reviews-batch", tmp_path)["table"]
    assert traffic.load("lookup-open-loop", tmp_path)["table"] == reviews
    assert traffic.load("tiny-mix", tmp_path)["table"] == reviews
