"""Deterministic concurrency harness for per-backend worker-pool dispatch.

The contract under test (see core/service.py): rows, ExecStats and EXPLAIN
output are byte-identical regardless of `dispatch_workers`, of speculative
flush timing, and of which worker thread finishes first.  Scripted
backends (tests/helpers.py) make answers and modeled latencies pure
functions of the prompt, and gate hooks force worst-case interleavings on
purpose.  Also covers: flush prioritization (smallest expected makespan
first, no starvation), PromptCache/StatisticsStore thread safety under
contention, and service lifecycle (drain-during-inflight, cancel after a
flush started, clean shutdown with non-empty queues and no leaked
threads).
"""
import dataclasses
import re
import threading
import time

import pytest

from helpers import LatencyScriptedPredictor, register_scripted
from hypothesis_compat import given, settings, st

from repro.core.database import IPDB
from repro.core.predict import _MISS, PromptCache
from repro.core.service import InferenceRequest, InferenceService
from repro.core.stats import CostModel, StatisticsStore
from repro.relational.table import Table


def echo_answers(instruction, rows):
    out = []
    for r in rows:
        joined = " ".join(f"{k}={v}" for k, v in sorted(r.items()))
        h = sum(map(ord, joined))
        out.append({"tag": f"t{h % 5}", "flag": h % 3 == 0,
                    "score": h % 7})
    return out


def make_db(*, chunk=2048, inflight=1, workers=1, max_dispatch=0,
            fast=None, slow=None, n=12):
    db = IPDB()
    db.register_table("T", Table.from_rows(
        [{"a": i, "txt": f"row {i}"} for i in range(n)]))
    fast = fast if fast is not None else \
        LatencyScriptedPredictor(echo_answers, base_latency_s=0.25)
    slow = slow if slow is not None else \
        LatencyScriptedPredictor(echo_answers, base_latency_s=1.0)
    register_scripted(db, "fastm", fast)
    register_scripted(db, "slowm", slow)
    db.set_option("chunk_size", chunk)
    db.set_option("inflight_windows", inflight)
    db.set_option("dispatch_workers", workers)
    db.set_option("max_dispatch_calls", max_dispatch)
    db.set_option("batch_size", 4)
    return db, fast, slow


Q_TWO_MODELS = ("SELECT a, LLM fastm (PROMPT 'one {tag VARCHAR} of "
                "{{txt}}') AS t1, LLM slowm (PROMPT 'two {score INTEGER} "
                "of {{txt}}') AS t2 FROM T")
Q_STACKED_SELECTS = ("SELECT a FROM T WHERE LLM fastm (PROMPT 'p "
                     "{flag BOOLEAN} of {{txt}}') = TRUE AND LLM slowm "
                     "(PROMPT 'q {flag BOOLEAN} of {{txt}}') = TRUE")


def _stats_dict(stats):
    d = dataclasses.asdict(stats)
    d.pop("wall_s")                    # real time: the honest exceptions
    d.pop("queue_wait_s")
    return d


# EXPLAIN prints the configured worker count in `-- dispatch --` (the
# configuration under test) and the binder's process-global __p_<n>
# column counter (naming, not behavior); normalize both, everything else
# must match byte-for-byte
_WORKERS_RE = re.compile(r"dispatch_workers=\d+")
_PCOUNT_RE = re.compile(r"__p_\d+_")


def _norm_explain(text: str) -> str:
    return _PCOUNT_RE.sub("__p_N_", _WORKERS_RE.sub("dispatch_workers=N",
                                                    text))


def _req(ex, prompt, *, instruction="i", dedup=True, stats_key=None):
    return InferenceRequest(
        model_name="m", instruction=instruction, prompt=prompt,
        schema=(("x", "INTEGER"),), num_rows=1, executor=ex,
        dedup=dedup, stats_key=stats_key)


# ---------------------------------------------------------------------------
# bit-identical results across the dispatch matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("query", [Q_TWO_MODELS, Q_STACKED_SELECTS])
def test_bit_identical_across_dispatch_matrix(query):
    """dispatch_workers ∈ {1, 2, 4} × inflight_windows ∈ {1, 4} × chunk
    sizes {1, 3, 2048}: rows are identical across the whole matrix, and
    for each (chunk, inflight) point the ExecStats and EXPLAIN output are
    bit-identical across worker counts — concurrency is pure mechanism."""
    reference_rows = None
    per_config = {}
    for chunk in (1, 3, 2048):
        for inflight in (1, 4):
            for workers in (1, 2, 4):
                db, _, _ = make_db(chunk=chunk, inflight=inflight,
                                   workers=workers)
                explain = _norm_explain(db.explain(query))
                r = db.sql(query)
                db.close()
                rows = r.table.rows()
                if reference_rows is None:
                    reference_rows = rows
                assert rows == reference_rows, \
                    f"rows diverged at chunk={chunk} inflight={inflight} " \
                    f"workers={workers}"
                key = (chunk, inflight)
                entry = (_stats_dict(r.stats), explain)
                if key not in per_config:
                    per_config[key] = entry
                else:
                    assert entry == per_config[key], \
                        f"stats/explain diverged at chunk={chunk} " \
                        f"inflight={inflight} workers={workers}"


def test_barrier_forced_concurrent_dispatch_identical_results():
    """Worst-case interleaving, forced: both backends' dispatch batches
    are held at a barrier until BOTH are mid-flight, and the slow backend
    finishes last.  Handle results must still resolve per-request
    correctly, on worker threads, with the same answers a synchronous
    service produces."""
    sync_ex = LatencyScriptedPredictor(echo_answers)
    svc_sync = InferenceService()
    sync_handles = svc_sync.submit(
        [_req(sync_ex, f"p{i}", instruction=f"i{i % 2}") for i in range(6)])
    svc_sync.flush()
    expected = [h.result().text for h in sync_handles]

    barrier = threading.Barrier(2, timeout=30)

    def gate(pred, prompts):
        barrier.wait()

    fast = LatencyScriptedPredictor(echo_answers, gate=gate)
    slow = LatencyScriptedPredictor(echo_answers, gate=gate,
                                    sleep_per_call_s=0.02)
    for ex in (fast, slow):
        ex.configure({"dispatch_workers": 4})
    svc = InferenceService()
    handles = []
    for i in range(6):
        ex = fast if i % 2 == 0 else slow
        h, _ = svc.submit_one(_req(ex, f"p{i}", instruction=f"i{i % 2}"))
        handles.append(h)
    svc.flush()                        # both queues scheduled concurrently
    got = [h.result().text for h in handles]
    svc.shutdown()
    assert got == expected
    assert not barrier.broken          # both dispatches really overlapped
    for ex in (fast, slow):
        assert len(ex.dispatch_log) == 1
        assert all("ipdb-dispatch" in t for t, _ in ex.dispatch_log)


def test_speculative_kick_preserves_rows_and_stats_in_sql_pipeline():
    """With max_dispatch set, operators kick() complete slices into the
    background after every submit.  Batch composition is invariant, so the
    full SQL pipeline produces identical rows AND identical ExecStats vs
    the synchronous single-worker run — while the dispatch log proves the
    work actually ran early, off the main thread."""
    ref_db, _, _ = make_db(chunk=3, inflight=4, workers=1, max_dispatch=1)
    ref = ref_db.sql(Q_TWO_MODELS)
    ref_db.close()

    db, fast, slow = make_db(chunk=3, inflight=4, workers=4, max_dispatch=1)
    r = db.sql(Q_TWO_MODELS)
    spec_batches = db.inference_service.stats.speculative_batches
    db.close()

    assert r.table.rows() == ref.table.rows()
    assert _stats_dict(r.stats) == _stats_dict(ref.stats)
    assert spec_batches > 0            # kick() really dispatched early
    worker_dispatches = [t for t, _ in fast.dispatch_log + slow.dispatch_log
                         if "ipdb-dispatch" in t]
    assert worker_dispatches           # ...and off the main thread


def test_speculative_kick_keeps_inflight_dedup_invariant():
    """Cross-window duplicate prompts + speculation: under synchronous
    dispatch the second window joins the first's still-queued handle.  A
    speculative kick dispatches that handle early, but it must stay
    joinable until the next flush — whether or not its batch already
    finished — so llm_calls and inflight_dedup_hits are identical across
    worker counts even for duplicate-heavy workloads."""
    results = {}
    for workers in (1, 4):
        db = IPDB()
        # windows of 3 rows render to identical marshaled prompts
        db.register_table("T", Table.from_rows(
            [{"a": i, "txt": f"dup{i % 3}"} for i in range(9)]))
        pred = LatencyScriptedPredictor(echo_answers)
        register_scripted(db, "m", pred)
        db.set_option("chunk_size", 3)
        db.set_option("inflight_windows", 3)
        db.set_option("dispatch_workers", workers)
        db.set_option("max_dispatch_calls", 1)
        db.set_option("batch_size", 4)
        r = db.sql("SELECT a, LLM m (PROMPT 'get {tag VARCHAR} of "
                   "{{txt}}') AS t FROM T")
        db.close()
        results[workers] = (r.table.rows(), _stats_dict(r.stats))
    assert results[1] == results[4]
    # the workload really exercised the dedup path
    assert results[1][1]["inflight_dedup_hits"] > 0
    assert results[1][1]["llm_calls"] == 1


def test_speculative_kick_unit_semantics():
    """kick() starts only the complete max_dispatch-sized slices a later
    flush would dispatch anyway; the trailing partial slice stays queued.
    No-ops: unbounded max_dispatch, synchronous backends, speculation
    disabled."""
    ex = LatencyScriptedPredictor(echo_answers)
    ex.configure({"dispatch_workers": 4})
    svc = InferenceService(max_dispatch=2)
    handles = svc.submit([_req(ex, f"p{i}") for i in range(5)])
    svc.kick()
    assert svc.wait_idle(timeout=30)
    assert [h.done for h in handles] == [True] * 4 + [False]
    assert svc.pending == 1
    assert svc.stats.speculative_batches == 2
    assert sorted(n for _, n in ex.dispatch_log) == [2, 2]
    svc.flush()                        # remainder dispatches normally
    assert svc.wait_idle(timeout=30)
    assert all(h.done for h in handles)
    assert sorted(n for _, n in ex.dispatch_log) == [1, 2, 2]
    svc.shutdown()

    # no-op cases: nothing may be dispatched by kick()
    for make in (
            lambda: (InferenceService(max_dispatch=0), 4),   # unbounded
            lambda: (InferenceService(max_dispatch=2), 1),   # sync backend
    ):
        svc2, workers = make()
        ex2 = LatencyScriptedPredictor(echo_answers)
        ex2.configure({"dispatch_workers": workers})
        svc2.submit([_req(ex2, f"p{i}") for i in range(4)])
        svc2.kick()
        assert svc2.wait_idle(timeout=5) and not ex2.dispatch_log
        assert svc2.pending == 4
        svc2.shutdown()
    svc3 = InferenceService(max_dispatch=2, speculative=False)
    ex3 = LatencyScriptedPredictor(echo_answers)
    ex3.configure({"dispatch_workers": 4})
    svc3.submit([_req(ex3, f"p{i}") for i in range(4)])
    svc3.kick()
    assert svc3.wait_idle(timeout=5) and not ex3.dispatch_log
    svc3.shutdown()


def test_async_executor_failure_surfaces_on_result():
    """A backend raising on a worker thread must surface the exception at
    result() on the submitting thread, and must not poison the in-flight
    map (later identical submits re-dispatch)."""

    class Boom(LatencyScriptedPredictor):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.fail = True

        def complete_many(self, prompts, *a, **kw):
            if self.fail:
                self.fail = False
                raise RuntimeError("backend down")
            return super().complete_many(prompts, *a, **kw)

    ex = Boom(echo_answers)
    ex.configure({"dispatch_workers": 4})
    svc = InferenceService()
    h, _ = svc.submit_one(_req(ex, "a"))
    svc.flush()                        # scheduled async; failure is remote
    with pytest.raises(RuntimeError, match="backend down"):
        h.result()
    h2, owned = svc.submit_one(_req(ex, "a"))
    assert owned                       # fresh handle, not a join
    svc.flush()
    assert h2.result().text
    svc.shutdown()


def test_inline_failure_does_not_strand_other_queues():
    """A synchronous backend raising mid-flush must not strand the other
    queues popped in the same flush: they still dispatch, the flush
    re-raises the failure, and the failed handle reports the real error
    (not a bogus 'cancelled')."""

    class Boom(LatencyScriptedPredictor):
        def complete_many(self, prompts, *a, **kw):
            raise RuntimeError("backend down")

    boom = Boom(echo_answers)
    ok = LatencyScriptedPredictor(echo_answers)
    svc = InferenceService()
    hb, _ = svc.submit_one(_req(boom, "a"))
    hg, _ = svc.submit_one(_req(ok, "b", instruction="other"))
    with pytest.raises(RuntimeError, match="backend down"):
        svc.flush()
    assert hg.done and hg.result().text
    with pytest.raises(RuntimeError, match="backend down"):
        hb.result()


# ---------------------------------------------------------------------------
# flush prioritization
# ---------------------------------------------------------------------------
def _priority_fixture(queue_specs):
    """Build a service + cost model with one queue per (n_calls, mean
    latency) spec; returns (svc, cost_model, specs)."""
    store = StatisticsStore()
    cm = CostModel(store, {"n_threads": 4})
    svc = InferenceService(stats_store=store, cost_model=cm)
    ex = LatencyScriptedPredictor(echo_answers)
    for qi, (n, lat) in enumerate(queue_specs):
        skey = ("m", f"instr{qi}")
        store.record_call(skey, 10, 5, lat)   # observed mean latency = lat
        for j in range(n):
            svc.submit_one(_req(ex, f"p{qi}.{j}",
                                instruction=f"instr{qi}", stats_key=skey))
    return svc, cm


def _check_priority(queue_specs):
    svc, cm = _priority_fixture(queue_specs)
    got = [qkey[1] for qkey in svc.prioritized()]
    expected = sorted(
        range(len(queue_specs)),
        key=lambda qi: (cm.queue_makespan(("m", f"instr{qi}"),
                                          queue_specs[qi][0]), qi))
    assert got == [f"instr{qi}" for qi in expected]
    svc.flush()                        # prioritization never starves:
    assert svc.pending == 0            # one flush drains every queue
    svc.shutdown()


def test_flush_priority_smallest_makespan_first_fixed_cases():
    _check_priority([(3, 2.0), (1, 0.125), (4, 0.25)])
    _check_priority([(2, 1.0), (2, 1.0), (1, 1.0)])   # stable tie-break
    _check_priority([(5, 0.5)])


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 5),
                          st.floats(0.05, 4.0, allow_nan=False)),
                min_size=1, max_size=6))
def test_flush_priority_matches_cost_model_sort(queue_specs):
    _check_priority(queue_specs)


# ---------------------------------------------------------------------------
# shared-state thread safety under contention
# ---------------------------------------------------------------------------
def test_prompt_cache_and_stats_store_stress():
    """8 threads hammer the LRU PromptCache (eviction churn over a key
    space larger than capacity, so touch-on-get races the delete) and the
    StatisticsStore (read-modify-write counters).  Totals must be exact:
    any lost update or KeyError fails the test."""
    pc = PromptCache(max_entries=64)
    store = StatisticsStore()
    n_threads, n_iter = 8, 400
    skey = ("m", "instr")
    errors = []
    start = threading.Barrier(n_threads)

    def worker(tid):
        try:
            start.wait()
            for i in range(n_iter):
                k = ("k", (tid * 31 + i) % 97)
                if pc.get(k) is _MISS:
                    pc.put(k, [i])
                store.record_call(skey, 3, 2, 0.25)
                store.record_predicate(skey, 4, 2)
                if i % 7 == 0:
                    store.record_retry(skey)
                if i % 11 == 0:
                    store.record_fallback(skey)
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    total = n_threads * n_iter
    rec = store.get(skey)
    assert rec.calls == total
    assert rec.in_tokens == 3 * total and rec.out_tokens == 2 * total
    assert rec.latency_s == 0.25 * total          # exact binary fraction
    assert rec.rows_in == 4 * total and rec.rows_passed == 2 * total
    assert rec.retries == n_threads * len(range(0, n_iter, 7))
    assert rec.fallbacks == n_threads * len(range(0, n_iter, 11))
    assert len(pc) <= 64
    assert pc.hits + pc.misses == total


# ---------------------------------------------------------------------------
# service lifecycle
# ---------------------------------------------------------------------------
def test_drain_waits_for_inflight_background_batches():
    started = threading.Event()
    release = threading.Event()

    def gate(pred, prompts):
        started.set()
        assert release.wait(30)

    ex = LatencyScriptedPredictor(echo_answers, gate=gate)
    ex.configure({"dispatch_workers": 4})
    svc = InferenceService()
    handles = svc.submit([_req(ex, f"p{i}") for i in range(3)])
    svc.flush()
    assert started.wait(30)
    assert svc.inflight_batches >= 1
    threading.Timer(0.1, release.set).start()
    svc.drain()                        # must block until the batch ends
    assert release.is_set()
    assert all(h.done for h in handles)
    assert svc.inflight_batches == 0
    svc.shutdown()


def test_cancel_after_flush_started_is_refused():
    """Cancelling a handle whose dispatch batch already started cannot
    recall it: cancel returns False, the batch completes, the result is
    still delivered.  A sibling handle still queued cancels normally."""
    hold = threading.Event()

    def gate(pred, prompts):
        assert hold.wait(30)

    ex = LatencyScriptedPredictor(echo_answers, gate=gate)
    ex.configure({"dispatch_workers": 4})
    svc = InferenceService(max_dispatch=2)
    ha, _ = svc.submit_one(_req(ex, "a"))
    hb, _ = svc.submit_one(_req(ex, "b"))
    hc, _ = svc.submit_one(_req(ex, "c", instruction="other"))
    svc.kick()                         # (a, b) now mid-flight, held at gate
    assert svc.inflight_batches == 1
    assert not svc.cancel(ha)          # flush already started: refused
    assert svc.cancel(hc)              # still queued: removable
    hold.set()
    assert ha.result().text and hb.result().text
    with pytest.raises(RuntimeError):
        hc.result()
    svc.shutdown()


def test_shutdown_with_nonempty_queues_leaks_no_threads():
    base_threads = threading.active_count()
    ex = LatencyScriptedPredictor(echo_answers)
    ex.configure({"dispatch_workers": 4})
    svc = InferenceService()
    # one async round so pool threads actually exist...
    svc.submit([_req(ex, f"w{i}") for i in range(4)])
    svc.flush()
    assert svc.wait_idle(timeout=30)
    assert threading.active_count() > base_threads
    # ...then leave fresh requests queued and shut down hard
    handles = svc.submit([_req(ex, f"q{i}") for i in range(3)])
    svc.shutdown(cancel_pending=True)
    for h in handles:
        with pytest.raises(RuntimeError):
            h.result()
    deadline = time.time() + 10
    while threading.active_count() > base_threads and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= base_threads, "leaked worker threads"
    svc.shutdown()                     # idempotent
    with pytest.raises(RuntimeError):
        svc.submit_one(_req(ex, "late"))


def test_shutdown_releases_lane_backlog_without_hanging():
    """Hard shutdown while a lane has MORE scheduled batches than workers:
    the running batches complete (a started dispatch is never interrupted),
    the backlog that will never be pumped resolves to a shutdown error —
    and shutdown itself does not hang on the orphaned accounting."""
    hold = threading.Event()

    def gate(pred, prompts):
        assert hold.wait(30)

    ex = LatencyScriptedPredictor(echo_answers, gate=gate, max_concurrency=2)
    ex.configure({"dispatch_workers": 2})
    svc = InferenceService(max_dispatch=1)
    handles = svc.submit([_req(ex, f"p{i}") for i in range(5)])
    svc.kick()                 # 2 running (held at gate), 3 lane backlog
    assert svc.inflight_batches == 5
    threading.Timer(0.1, hold.set).start()
    svc.shutdown(cancel_pending=True)      # must not hang
    assert handles[0].result().text and handles[1].result().text
    for h in handles[2:]:
        with pytest.raises(RuntimeError, match="shut down"):
            h.result()


def test_graceful_shutdown_drains_queued_work():
    ex = LatencyScriptedPredictor(echo_answers)
    ex.configure({"dispatch_workers": 2})
    svc = InferenceService()
    handles = svc.submit([_req(ex, f"p{i}") for i in range(3)])
    svc.shutdown()                     # default: drain, then close
    assert all(h.done for h in handles)
    assert all(h.result().text for h in handles)


def test_database_close_joins_dispatch_threads():
    base_threads = threading.active_count()
    with make_db(workers=4)[0] as db:
        r = db.sql(Q_TWO_MODELS)
        assert len(r.table) == 12
    deadline = time.time() + 10
    while threading.active_count() > base_threads and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= base_threads
