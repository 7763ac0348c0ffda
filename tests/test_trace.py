"""The program's spans and counters: every span it opens is one of
``repro.core.trace.SPANS`` and nests as documented, one ``engine.tick`` per
decode step counted; the queue wait and the decode counters reach
``ExecStats``."""
import json
import sys
import threading
import time
from pathlib import Path

import jax
import pytest

from repro.core import trace
from repro.core.database import IPDB
from repro.core.executors import CallResult, Predictor
from repro.relational.table import Table

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))   # bench/
from bench import spans as bench_spans  # noqa: E402
from bench import trace_reduce  # noqa: E402

from helpers import register_scripted  # noqa: E402

PROMPT = "'name the {color VARCHAR} of {{name}}'"


def traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the program spans it opened."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return bench_spans.read(trace_reduce.find_xplane(str(tmp_path)))


def jax_db(layout: str) -> IPDB:
    opts = {"enable_pilot": False}
    if layout == "paged":
        # a pinned pool too small to keep every prompt's pages: slot fills
        # evict radix leaves
        opts.update(kv_layout="paged", kv_prefix_mode="radix",
                    kv_pool_pages=18)
    db = IPDB(session_options=opts)
    db.register_table("Items", Table.from_rows(
        [{"id": i, "name": f"item number {i} " * (1 + i % 3)}
         for i in range(8)]))
    db.sql("CREATE LLM MODEL m PATH 'jax:olmo-1b' ON PROMPT OPTIONS "
           "{ 'batch_size': 2, 'max_str': 4, 'num_slots': 2, "
           "'temperature': 0 }")
    return db


def decode_totals(db) -> tuple:
    return tuple(sum(getattr(e.total, k) for e in db._jax_engines.values())
                 for k in COUNTERS)


COUNTERS = ("decode_steps", "decode_rows", "decode_slots")
Q_ALL = f"SELECT id, LLM m (PROMPT {PROMPT}) AS c FROM Items"
# another instruction: compiles the same programs, shares no cached answer
Q_WARM = "SELECT id, LLM m (PROMPT 'guess the {color VARCHAR} of " \
    "{{name}}') AS c FROM Items"


def contained(inner, outer) -> bool:
    return inner[1] == outer[1] and outer[2] <= inner[2] and \
        inner[3] <= outer[3]


def assert_nesting(spans):
    """tick > sample > mask; every slot fill holds its prefill."""
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    for m in by["engine.mask"]:
        sample = [s for s in by["engine.sample"] if contained(m, s)]
        assert len(sample) == 1
        assert any(contained(sample[0], t) for t in by["engine.tick"])
    for f in by.get("engine.fill", []):
        assert any(contained(p, f) for p in by["engine.prefill"])


def sql_run(tmp_path, layout):
    """A batched query (the continuous batcher) and a one-row query
    (``generate``) through ``IPDB.sql``, traced."""
    db = jax_db(layout)
    db.sql(Q_WARM)
    before = decode_totals(db)
    stats = []

    def work():
        for where in ("", " WHERE id = 3"):
            stats.append(db.sql(Q_ALL + where).stats)

    spans = traced(tmp_path, work)
    return {"spans": spans, "stats": stats,
            "engine": tuple(a - b for a, b in
                            zip(decode_totals(db), before))}


def stream_run(tmp_path):
    """A one-row query (dense ``generate``) and a select on a backend with
    two dispatch workers through ``IPDB.stream``, traced."""
    db = jax_db("dense")
    db.register_oracle("slow", lambda ins, rows: [{"flag": True}
                                                   for _ in rows],
                       sleep_per_call_s=0.05)
    db.sql("CREATE LLM MODEL o PATH 'oracle:slow' ON PROMPT "
           "OPTIONS { 'dispatch_workers': 2 }")
    db.sql(Q_WARM + " WHERE id = 5")
    before = decode_totals(db)
    stats = []

    def work():
        for q in (Q_ALL + " WHERE id = 5",
                  "SELECT id FROM Items WHERE LLM o (PROMPT 'is "
                  "{flag BOOLEAN} {{name}}') = TRUE"):
            s = db.stream(q)
            list(s.chunks())
            stats.append(s.stats)

    spans = traced(tmp_path, work)
    return {"spans": spans, "stats": stats,
            "engine": tuple(a - b for a, b in
                            zip(decode_totals(db), before))}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    return {"dense": sql_run(tmp_path_factory.mktemp("dense"), "dense"),
            "paged": sql_run(tmp_path_factory.mktemp("paged"), "paged"),
            "stream": stream_run(tmp_path_factory.mktemp("stream"))}


def test_every_listed_span_is_opened_and_no_other(runs):
    seen = [{s[0] for s in r["spans"]} for r in runs.values()]
    assert all(names <= set(trace.SPANS) for names in seen)
    assert set().union(*seen) == set(trace.SPANS)
    assert len(set(trace.SPANS)) == len(trace.SPANS)
    assert all(f"``{n}``" in trace.__doc__ for n in trace.SPANS)


@pytest.mark.parametrize("run", ["dense", "paged", "stream"])
def test_spans_nest(runs, run):
    assert_nesting(runs[run]["spans"])


@pytest.mark.parametrize("run", ["dense", "paged", "stream"])
def test_one_tick_per_decode_step(runs, run):
    r = runs[run]
    assert r["engine"][0] > 0
    assert sum(1 for s in r["spans"] if s[0] == "engine.tick") == \
        r["engine"][0]


@pytest.mark.parametrize("run", ["dense", "paged", "stream"])
def test_decode_counters_reach_exec_stats(runs, run):
    r = runs[run]
    got = tuple(sum(getattr(st, k) for st in r["stats"]) for k in COUNTERS)
    assert got == r["engine"]
    steps, rows, slots = got
    assert 0 < rows <= slots and slots % steps == 0


def test_stream_waits_are_spans(runs):
    names = {s[0] for s in runs["stream"]["spans"]}
    assert {"await_plan_lock", "await_result"} <= names
    assert all(st.queue_wait_s >= 0 for r in runs.values()
               for st in r["stats"])


class _Gate(Predictor):
    """A backend whose first two dispatches block until released: with two
    dispatch workers they hold the backend's whole lane."""
    max_concurrency = 2

    def __init__(self):
        self.entered = threading.Semaphore(0)
        self.release = threading.Event()
        self.calls = 0
        self._lock = threading.Lock()

    def complete(self, prompt, schema, num_rows, **kw):
        return CallResult(json.dumps({"flag": True}), 1, 1, 0.0, 0.0)

    def complete_many(self, prompts, schema, num_rows_list, **kw):
        with self._lock:
            self.calls += 1
            hold = self.calls <= 2
        if hold:
            self.entered.release()
            assert self.release.wait(10)
        return super().complete_many(prompts, schema, num_rows_list, **kw)


def test_queue_wait_counts_time_behind_a_held_lane():
    block = 0.3
    db = IPDB(session_options={"enable_pilot": False,
                               "dispatch_workers": 2})
    db.register_table("T", Table.from_rows([{"a": 1, "txt": "x"}]))
    gate = _Gate()
    register_scripted(db, "m", gate)
    sql = ("SELECT a FROM T WHERE LLM m (PROMPT 'is {flag BOOLEAN} "
           "{{txt}}') = TRUE")
    out = {}

    def one(k):
        s = db.stream(sql, tenant=f"t{k}")
        list(s.chunks())
        out[k] = s.stats

    threads = [threading.Thread(target=one, args=(k,)) for k in range(3)]
    for t in threads[:2]:
        t.start()
    for _ in range(2):
        assert gate.entered.acquire(timeout=10)
    threads[2].start()
    deadline = time.monotonic() + 10
    while db.inference_service.stats.submitted < 3:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    time.sleep(block)
    gate.release.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert out[2].queue_wait_s >= block
    assert out[0].queue_wait_s < block and out[1].queue_wait_s < block
    total = db.inference_service.stats.queue_wait_s
    assert total == pytest.approx(sum(s.queue_wait_s for s in out.values()))
    db.close()
