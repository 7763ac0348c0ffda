"""Program spans against the device's busy time, on synthetic traces."""
import pytest

from bench import spans

MS = 1_000_000


def raw(ops, bench=(), modules=()):
    return {"spans": [("bench.window", 0, 100)] + list(bench),
            "devices": {"/device:TPU:0": {"ops": list(ops),
                                          "modules": list(modules)}}}


def test_self_time_is_per_thread():
    program = [("engine.tick", 1, 0, 100), ("engine.sample", 1, 10, 40),
               ("engine.mask", 1, 15, 25), ("engine.step", 1, 50, 90),
               ("engine.tick", 2, 20, 60)]
    red = spans.reduce(program, raw([(50, 90)]))
    t = red["spans"]
    assert t["engine.tick"]["n"] == 2
    assert t["engine.tick"]["total_s"] == pytest.approx(140e-9)
    # thread 1's tick less its sample and step; thread 2's tick whole
    assert t["engine.tick"]["self_s"] == pytest.approx((30 + 40) * 1e-9)
    assert t["engine.sample"]["self_s"] == pytest.approx(20e-9)
    assert t["engine.mask"]["self_s"] == pytest.approx(10e-9)
    # idle inside self time: all of thread 1's tick remainder, and thread
    # 2's tick up to the step's operations
    assert t["engine.tick"]["idle_s"] == pytest.approx((30 + 30) * 1e-9)
    assert t["engine.step"]["idle_s"] == 0
    # the ticks' union is the window; the device ran 40 of its 100
    assert red["tick_idle_share"] == pytest.approx(60.0)


def test_idle_between_a_programs_operations_is_told_apart():
    program = [("engine.step", 1, 0, 100)]
    red = spans.reduce(program, raw([(10, 30), (40, 60), (80, 90)],
                                    modules=[("decode_step", 10, 60),
                                             ("decode_step", 80, 90)]))
    row = red["spans"]["engine.step"]
    assert row["idle_s"] == pytest.approx(50e-9)
    # 30-40 inside the first program; the rest with no program running
    assert row["op_gap_s"] == pytest.approx(10e-9)


def test_gaps_are_named_by_the_innermost_program_span():
    program = [("engine.tick", 1, 0, 60), ("engine.mask", 1, 5, 15)]
    red = spans.reduce(program, raw(
        [(0, 5), (15, 20), (30, 100)], bench=[("bench.dispatch", 0, 100)]))
    assert red["idle_gaps"] == [["engine.mask", pytest.approx(10e-9), None],
                                ["engine.tick", pytest.approx(10e-9), None]]
    assert red["dispatch_gaps"] == red["idle_gaps"]
    # outside any program span the benchmark's span names the gap
    red = spans.reduce([("engine.run", 1, 10, 100)],
                       raw([(0, 20)], bench=[("bench.dispatch", 0, 100)]))
    # a gap 10 ns into the run, named by the run
    assert red["idle_gaps"] == [["engine.run", pytest.approx(80e-9),
                                 pytest.approx(10e-9)]]
    red = spans.reduce([("engine.tick", 1, 0, 10)],
                       raw([(0, 20)], bench=[("bench.dispatch", 0, 100)]))
    assert red["idle_gaps"] == [["dispatch", pytest.approx(80e-9), None]]


def test_waiting_names_a_gap_only_when_no_work_is_open():
    program = [("engine.step", 1, 0, 40), ("await_result", 2, 10, 90),
               ("await_plan_lock", 3, 60, 70)]
    red = spans.reduce(program, raw(
        [(0, 5), (35, 50), (75, 100)],
        bench=[("bench.dispatch", 40, 55), ("bench.await_arrival", 0, 100)]))
    names = dict((round(g[1] * 1e9), g[0]) for g in red["idle_gaps"])
    # 5-35 inside the step; 50-75 only waits, the latest-started wins
    assert names == {30: "engine.step", 25: "await_plan_lock"}
    assert red["dispatch_gaps"] == []


def test_plan_time_per_query():
    program = [("sql.parse", 1, 0, 2 * MS), ("sql.bind", 1, 2 * MS, 3 * MS),
               ("sql.optimize", 1, 3 * MS, 10 * MS),
               ("service.dispatch", 1, 4 * MS, 9 * MS),
               ("sql.parse", 2, 20 * MS, 21 * MS)]
    r = raw([(0, 30 * MS)])
    r["spans"] = [("bench.window", 0, 30 * MS)]
    red = spans.reduce(program, r)
    # (2 + 1 + 2 (optimize less its pilot dispatch) + 1) ms over 2 queries
    assert red["plan_ms"] == pytest.approx(3.0)
    assert red["tick_idle_share"] is None


def test_spans_outside_the_window_are_cut():
    red = spans.reduce([("engine.tick", 1, -50, 50),
                        ("engine.tick", 1, 120, 130)], raw([]))
    assert red["spans"]["engine.tick"]["n"] == 1
    assert red["spans"]["engine.tick"]["total_s"] == pytest.approx(50e-9)
    with pytest.raises(ValueError):
        spans.reduce([], {"spans": [], "devices": {}})


@pytest.mark.parametrize("cell", ["olmo-1b.reviews-batch",
                                  "olmo-1b.lookup-open-loop"])
def test_traced_smoke_cell_reads_program_spans(cell):
    import time
    from bench import harness
    plan = harness.cell_plan(harness.load_benchmark(), cell)
    out = spans.traced_cell(plan, 2147483901, 2.0,
                            t_start=time.perf_counter(), smoke=True)
    prog = out["program"]
    assert list(out)[-2:] == ["program", "checks"]
    assert {"sql.parse", "service.dispatch", "engine.tick",
            "engine.step"} <= set(prog["spans"])
    assert prog["plan_ms"] > 0 and 0 <= prog["tick_idle_share"] <= 100
    assert all(g[0] != "no span" for g in prog["dispatch_gaps"])
    e2e = {m["name"] for m in plan["end_to_end"]} - {"setup_s"}
    assert set(prog["traced"]) == e2e
    assert all(v is not None for v in prog["traced"].values())
