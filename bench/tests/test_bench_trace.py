"""Trace reduction on a small trace recorded on the CPU."""
import time

import pytest

from bench import trace_reduce

CPU_LINES = {"device_prefix": "/host:CPU", "ops_line": "tf_XLAPjRtCpuClient",
             "modules_line": ""}


def test_interval_helpers():
    assert trace_reduce.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]
    assert trace_reduce.clip([(0, 4), (5, 10)], 2, 7) == [(2, 4), (5, 7)]
    assert trace_reduce.gaps([(2, 4), (5, 7)], 0, 9) == \
        [(0, 2), (4, 5), (7, 9)]
    assert trace_reduce.program_name("jit_paged_decode_step(42)") == \
        "paged_decode_step"


def test_reduce_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sample"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    raw = trace_reduce.read(trace_reduce.find_xplane(str(tmp_path)),
                            **CPU_LINES)
    red = trace_reduce.reduce(raw)
    assert 0.09 < red["window_s"] < 5.0
    assert 0.0 < red["busy_s"] < red["window_s"] - 0.08
    gaps = red["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= trace_reduce.TOP
    # the three sleeps are the longest gaps, each inside its sample span
    assert [g[0] for g in gaps[:3]] == ["sample"] * 3
    assert all(g[1] >= 0.025 for g in gaps[:3])


def test_reduce_needs_window_and_device():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {"d": {"ops": [], "modules": []}},
                             "spans": []})
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {},
                             "spans": [("bench.window", 0, 10)]})


def test_program_times_and_busy_union():
    raw = {"spans": [("bench.window", 0, 100), ("bench.sample", 40, 80)],
           "devices": {"/device:TPU:0": {
               "ops": [(10, 20), (15, 30), (90, 120)],
               "modules": [("decode_step", 10, 30), ("decode_step", 90, 120),
                           ("prefill_step", 0, 5)]}}}
    red = trace_reduce.reduce(raw)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["program_s"]["decode_step"] == pytest.approx(30e-9)
    assert red["program_n"]["decode_step"] == 2
    assert red["breakdown"]["idle_gaps"][0] == ["sample", pytest.approx(60e-9)]


def test_waiting_names_a_gap_only_when_no_work_is_open():
    raw = {"spans": [("bench.window", 0, 100), ("bench.dispatch", 20, 95),
                     ("bench.await_arrival", 50, 90),
                     ("bench.await_arrival", 96, 100)],
           "devices": {"/device:TPU:0": {"ops": [(0, 60), (80, 97)],
                                         "modules": []}}}
    gaps = trace_reduce.reduce(raw)["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["dispatch", "await_arrival"]
