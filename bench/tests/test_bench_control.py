"""The control: the float8 reference in the program's place, at the same
positions of the same served requests, comes out not correct under the
cell's own limits, while the program's readings on those requests are
within them (smoke sizes, on the CPU)."""
import pytest

from bench.tests.test_bench_faults import CELLS, run, small_plan


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    plan = small_plan(cell)
    lim = plan["limits"]
    out = run(plan, seed=2 ** 31 + 9, control=True)
    c = out["control"]
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_err"]["value"] == c["control_err"]
    assert c["err"] <= lim["max_logit_err"], c
    assert c["gap"] <= lim["max_logit_gap"], c
