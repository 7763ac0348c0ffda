"""Front-door serving tier: streaming HTTP sessions, admission control,
end-to-end cancellation, DRR fairness, and the multi-session determinism
contract (PR 8).

Determinism extends PR 4's harness (tests/helpers.py): N concurrent
streaming sessions over scripted backends must produce rows and
ExecStats byte-identical to running the same queries serially, for every
dispatch_workers setting — sessions are tagged into their own service
queues, so no interleaving can change batch composition or accounting.
Cancellation tests force worst-case orderings with gate hooks (cancel
while a flush is mid-executor-call) and assert the "within one flush"
contract: the running batch completes, nothing new dispatches, queued
requests are dropped and handles released.
"""
import threading
import time

import pytest

from helpers import (LatencyScriptedPredictor, drain_stream,
                     register_scripted, run_sessions, stream_stats_dict)

from repro.core.cancel import QueryCancelled
from repro.core.database import IPDB
from repro.frontdoor import (DeficitRoundRobin, FifoGate, FrontDoor,
                             FrontDoorClient, QueryRejected)
from repro.relational.table import Table


def scripted_answers(instruction, rows):
    out = []
    for r in rows:
        joined = " ".join(f"{k}={v}" for k, v in sorted(r.items()))
        h = sum(map(ord, joined)) + sum(map(ord, instruction))
        out.append({"tag": f"t{h % 5}", "flag": h % 3 == 0,
                    "score": h % 7})
    return out


def make_db(*, n=24, chunk=4, workers=1, predictor=None, pilot=False):
    db = IPDB()
    db.register_table("T", Table.from_rows(
        [{"a": i, "txt": f"row {i}"} for i in range(n)]))
    pred = predictor if predictor is not None else \
        LatencyScriptedPredictor(scripted_answers, base_latency_s=0.25)
    register_scripted(db, "m", pred)
    db.set_option("chunk_size", chunk)
    db.set_option("batch_size", 4)
    db.set_option("dispatch_workers", workers)
    db.set_option("enable_pilot", pilot)
    return db, pred


def q(instr: str) -> str:
    return ("SELECT a, LLM m (PROMPT '" + instr +
            " {tag VARCHAR} of {{txt}}') AS t FROM T")


def wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


# ---------------------------------------------------------------------------
# fairness gates (unit)
# ---------------------------------------------------------------------------
def test_fifo_gate_grants_in_arrival_order():
    gate = FifoGate(1)
    order = []
    gate.acquire("a")

    def worker(tag):
        gate.acquire(tag)
        order.append(tag)
        gate.release(tag, cost=1.0)

    threads = []
    for tag in ["x", "y", "z"]:
        t = threading.Thread(target=worker, args=(tag,))
        t.start()
        time.sleep(0.05)               # deterministic arrival order
        threads.append(t)
    gate.release("a", cost=1.0)
    for t in threads:
        t.join(timeout=5)
    assert order == ["x", "y", "z"]


def test_drr_light_tenant_overtakes_indebted_heavy_tenant():
    """Post-paid DRR: after the heavy tenant is charged a large cost, the
    light tenant's queued waiters win the next slots even though they
    arrived later."""
    gate = DeficitRoundRobin(1, quantum=2.0)
    order = []
    gate.acquire("heavy")

    def worker(tenant, label):
        assert gate.acquire(tenant)
        order.append(label)
        gate.release(tenant, cost=1.0)

    threads = []
    # heavy's backlog arrives first, light's afterwards
    for tenant, label in [("heavy", "h1"), ("heavy", "h2"),
                          ("light", "l1"), ("light", "l2")]:
        t = threading.Thread(target=worker, args=(tenant, label))
        t.start()
        time.sleep(0.05)
        threads.append(t)
    gate.release("heavy", cost=50.0)   # heavy just consumed a huge chunk
    for t in threads:
        t.join(timeout=5)
    # light drains completely before heavy's backlog continues
    assert order[:2] == ["l1", "l2"]
    assert sorted(order[2:]) == ["h1", "h2"]
    assert gate.grants["light"] == 2 and gate.grants["heavy"] == 3


def test_drr_weights_bias_replenishment():
    """With weight 3 vs 1 and everyone in debt, the heavier-weighted
    tenant replenishes past zero first and wins the slot."""
    gate = DeficitRoundRobin(1, quantum=1.0, weights={"gold": 3.0})
    gate.acquire("seed")               # hold the only slot
    got = []

    def worker(tenant):
        assert gate.acquire(tenant)
        got.append(tenant)
        gate.release(tenant, cost=0.0)

    threads = []
    for tenant in ["basic", "gold"]:
        t = threading.Thread(target=worker, args=(tenant,))
        t.start()
        time.sleep(0.05)
        threads.append(t)
    # both start at credit 0 -> replenish: basic +1, gold +3 -> gold wins
    gate.release("seed", cost=5.0)
    for t in threads:
        t.join(timeout=5)
    assert got[0] == "gold"


def test_gate_acquire_abort_event_returns_false():
    gate = DeficitRoundRobin(1)
    assert gate.acquire("a")
    abort = threading.Event()
    res = {}

    def worker():
        res["got"] = gate.acquire("a", abort=abort)

    t = threading.Thread(target=worker)
    t.start()
    time.sleep(0.05)
    abort.set()
    gate.kick()                        # what a CancelScope callback does
    t.join(timeout=5)
    assert res["got"] is False
    assert gate.waiting() == 0
    gate.release("a")


# ---------------------------------------------------------------------------
# streaming sessions over HTTP
# ---------------------------------------------------------------------------
def test_http_stream_rows_and_exec_stats_trailer():
    db, pred = make_db()
    with db, FrontDoor(db, max_sessions=2, max_queued=2) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        h = cli.query(q("one"), tenant="acme")
        assert h.session_id.startswith("fd")
        frames = list(h.frames())
        chunks = [f for f in frames if f["type"] == "chunk"]
        trailer = frames[-1]
        assert trailer["type"] == "trailer" and trailer["status"] == "ok"
        assert len(chunks) == 24 // 4          # one frame per 4-row chunk
        assert [c["seq"] for c in chunks] == list(range(len(chunks)))
        rows = [r for c in chunks for r in c["rows"]]
        assert [r["a"] for r in rows] == list(range(24))
        # the trailer carries the same ExecStats the Python API reports
        ref = db.sql(q("one"))                 # fully prompt-cached rerun
        assert set(trailer["stats"]) == (
            set(stream_stats_dict(ref.stats)) | {"wall_s", "queue_wait_s"})
        assert trailer["stats"]["llm_calls"] == 24 // 4
        assert trailer["stats"]["cancelled"] is False
        assert trailer["rows"] == 24


def test_http_explain_trailer_carries_plan():
    db, _ = make_db()
    with db, FrontDoor(db) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        res = cli.query(q("exp"), explain=True).result()
        assert res["status"] == "ok"
        assert "-- physical --" in res["plan"]
        assert "-- dispatch --" in res["plan"]


def test_http_streams_incrementally_not_all_at_end():
    """Chunk frames must arrive while later chunks are still being
    produced: hold the backend after the first dispatch and check the
    first frame is already readable."""
    release = threading.Event()
    seen = []

    def gate(pred, prompts):
        seen.append(len(prompts))
        if len(seen) > 1:              # first batch passes, rest wait
            assert release.wait(timeout=10)

    pred = LatencyScriptedPredictor(scripted_answers, gate=gate)
    db, _ = make_db(predictor=pred)
    with db, FrontDoor(db) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        h = cli.query(q("inc"))
        frames = h.frames()
        first = next(frames)
        assert first["type"] == "chunk" and len(first["rows"]) == 4
        release.set()
        rest = list(frames)
        assert rest[-1]["status"] == "ok"
        assert sum(len(f["rows"]) for f in rest
                   if f["type"] == "chunk") == 20


def test_admission_control_rejects_with_429():
    """max_sessions=1, max_queued=0: while one session is pinned inside
    the backend, a second POST /query is rejected up front."""
    release = threading.Event()

    def gate(pred, prompts):
        assert release.wait(timeout=10)

    pred = LatencyScriptedPredictor(scripted_answers, gate=gate)
    db, _ = make_db(predictor=pred)
    with db, FrontDoor(db, max_sessions=1, max_queued=0) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        h1 = cli.query(q("adm"))
        deadline = time.time() + 5
        while fd._active < 1 and time.time() < deadline:
            time.sleep(0.01)
        with pytest.raises(QueryRejected) as ei:
            cli.query(q("adm2"))
        assert ei.value.status == 429
        release.set()
        assert h1.result()["status"] == "ok"
        assert wait_for(lambda: cli.server_stats().get("completed") == 1)
        assert cli.server_stats()["rejected"] == 1


def test_delete_cancels_within_one_flush():
    """DELETE /query/<id> while the session is mid-flush: the running
    batch completes, no further batch dispatches for that session, its
    queued handles are released, and the trailer reports cancelled."""
    entered = threading.Event()
    release = threading.Event()

    def gate(pred, prompts):
        entered.set()
        assert release.wait(timeout=10)

    pred = LatencyScriptedPredictor(scripted_answers, gate=gate)
    db, _ = make_db(predictor=pred)
    with db, FrontDoor(db) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        h = cli.query(q("del"))
        assert entered.wait(timeout=10)        # first flush is running
        dispatched_before = len(pred.dispatch_log) + 1  # the one in-flight
        assert cli.cancel(h.session_id)
        release.set()                          # let the running batch end
        res = h.result()
        assert res["status"] == "cancelled"
        assert res["stats"]["cancelled"] is True
        # within one flush: the in-flight batch was the LAST dispatch
        time.sleep(0.1)
        assert len(pred.dispatch_log) == dispatched_before
        assert db.inference_service.session_pending(h.session_id) == 0
        assert wait_for(
            lambda: cli.server_stats().get("cancelled_sessions") == 1)


def test_client_disconnect_cancels_session():
    """Dropping the socket mid-stream must cancel the session exactly
    like an explicit DELETE: dispatch stops within one flush."""
    entered = threading.Event()
    release = threading.Event()

    def gate(pred, prompts):
        entered.set()
        assert release.wait(timeout=10)

    pred = LatencyScriptedPredictor(scripted_answers, gate=gate)
    db, _ = make_db(predictor=pred)
    with db, FrontDoor(db) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        h = cli.query(q("dis"))
        assert entered.wait(timeout=10)
        dispatched_cap = len(pred.dispatch_log) + 1
        h.abort()                              # EOF on the server side
        # wait until the server noticed and fired the scope, THEN let the
        # in-flight batch finish — worst-case ordering on purpose
        assert wait_for(lambda: fd._sessions.get(h.session_id) is None
                        or fd._sessions[h.session_id].scope.cancelled)
        release.set()
        assert wait_for(lambda: fd._active == 0 and not fd._sessions)
        assert len(pred.dispatch_log) <= dispatched_cap
        assert wait_for(
            lambda: cli.server_stats().get("cancelled_sessions") == 1)


# ---------------------------------------------------------------------------
# multi-session determinism (PR 4 harness, extended)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_multi_session_rows_and_stats_match_serial(workers):
    """N concurrent sessions x dispatch_workers: rows and ExecStats are
    byte-identical to running the same queries serially on a fresh
    database.  Distinct per-session instructions keep prompt-cache keys
    disjoint, so the contract covers scheduling, not cache luck."""
    queries = [("acme", q("alpha")), ("acme", q("beta")),
               ("zeta", q("gamma")), ("", q("delta"))]

    def fresh():
        return make_db(n=24, chunk=4, workers=workers)[0]

    db_serial = fresh()
    with db_serial:
        expect = run_sessions(db_serial, queries, concurrent=False)
    for round_no in range(3):           # several interleavings
        db_conc = fresh()
        barrier = threading.Barrier(len(queries))
        with db_conc:
            got = run_sessions(db_conc, queries, concurrent=True,
                               start_barrier=barrier)
        assert got == expect, f"divergence on round {round_no}"


@pytest.mark.parametrize("workers", [1, 2])
def test_cancel_mid_flush_is_deterministic_and_bounded(workers):
    """Barrier-forced worst case: session B cancels while its flush is
    inside the executor.  The surviving session's rows/stats are
    untouched, B stops within one flush, and B's handles are released."""
    entered = threading.Event()
    release = threading.Event()

    def gate(pred, prompts):
        # only session B's prompts gate (distinct instruction text)
        if any("victim" in p for p in prompts):
            entered.set()
            assert release.wait(timeout=10)

    pred = LatencyScriptedPredictor(scripted_answers, gate=gate)
    db, _ = make_db(n=24, chunk=4, workers=workers, predictor=pred)
    with db:
        survivor_rows, survivor_stats = drain_stream(
            db.stream(q("bystander")))
        stream_b = db.stream(q("victim"), tenant="b")
        outcome = {}

        def run_b():
            try:
                outcome["res"] = drain_stream(stream_b)
            except QueryCancelled as e:
                outcome["err"] = e

        t = threading.Thread(target=run_b)
        t.start()
        assert entered.wait(timeout=10)       # B is mid-executor-call
        stream_b.cancel("test")
        release.set()
        t.join(timeout=10)
        assert not t.is_alive()
        rows_b, stats_b = outcome["res"]
        assert stats_b.cancelled is True
        # within one flush: every dispatched batch for B happened before
        # the cancel was observed; nothing dispatched afterwards
        dispatched_after = len(pred.dispatch_log)
        time.sleep(0.1)
        db.inference_service.flush()
        assert len(pred.dispatch_log) == dispatched_after
        assert db.inference_service.session_pending(stream_b.session) == 0
        # the bystander session, re-run on a fresh identical db, is
        # byte-identical — the cancelled neighbor never leaked into it
        db2, _ = make_db(n=24, chunk=4, workers=workers)
        with db2:
            rows2, stats2 = drain_stream(db2.stream(q("bystander")))
        assert rows2 == survivor_rows
        assert stream_stats_dict(stats2) == stream_stats_dict(
            survivor_stats)


def test_stream_rejects_non_select():
    db, _ = make_db()
    with db:
        with pytest.raises(ValueError):
            db.stream("SET chunk_size = 8")


# ---------------------------------------------------------------------------
# resilience (PR 10): breaker shed, HTTP deadlines, cancel racing chaos
# ---------------------------------------------------------------------------
def test_breaker_open_sheds_with_503_and_retry_after():
    """While any backend breaker is open, POST /query is shed with 503 +
    Retry-After BEFORE admission; recovery reopens the front door."""
    db, _ = make_db()
    with db, FrontDoor(db, retry_after_s=2) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        b = db.inference_service.breaker_for("m")
        for _ in range(3):
            b.record_failure()           # trip the breaker by hand
        with pytest.raises(QueryRejected) as ei:
            cli.query(q("shed"))
        assert ei.value.status == 503
        assert wait_for(
            lambda: cli.server_stats().get("rejected_breaker") == 1)
        b.record_success()               # backend recovered
        res = cli.query(q("shed")).result()
        assert res["status"] == "ok" and res["rows"] == 24


def test_http_deadline_ms_degrades_to_nulls_not_errors():
    """A 1ms deadline_ms in the POST body flows through the session into
    the operators: the query still completes (status ok) with dropped
    work degraded to NULLs and the drops visible in the trailer stats."""
    pred = LatencyScriptedPredictor(scripted_answers, sleep_per_call_s=0.05)
    db, _ = make_db(predictor=pred)
    with db, FrontDoor(db) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        res = cli.query(q("dlh"), deadline_ms=1).result()
        assert res["status"] == "ok"
        assert res["rows"] == 24
        assert res["stats"]["deadline_drops"] > 0
        assert len(pred.dispatch_log) <= 1


def test_cancel_races_injected_faults_without_leaks():
    """DELETE /query while the backend is mid-chaos (seeded transient
    faults + per-call wall time): the session terminates cleanly, its
    queued handles are released within one flush, and the database keeps
    serving afterwards."""
    from repro.core.faults import FaultInjector
    inj = FaultInjector(
        LatencyScriptedPredictor(scripted_answers, base_latency_s=0.25,
                                 sleep_per_call_s=0.02),
        seed=5, transient_rate=0.4)
    db, _ = make_db(predictor=inj, workers=2)
    with db, FrontDoor(db) as fd:
        cli = FrontDoorClient(fd.host, fd.port)
        h = cli.query(q("race"))
        # cancel only after chaos has started (faults possibly in flight)
        assert wait_for(lambda: inj.counters["calls"] > 0)
        cli.cancel(h.session_id)
        res = h.result()
        assert res["status"] in ("cancelled", "ok")
        assert wait_for(lambda: db.inference_service.session_pending(
            h.session_id) == 0)
        assert wait_for(lambda: fd._active == 0 and not fd._sessions)
        # the race leaked nothing: a follow-up query serves every row
        after = db.sql(q("after"))
        assert len(after.table.rows()) == 24
        assert all(r["t"] is not None for r in after.table.rows())


def test_periodic_snapshots_persist_warm_state(tmp_path):
    """FrontDoor(snapshot_every_s=...) snapshots the db's warm state in
    the background and once more at stop(); a restarted db+front door
    serves the same query without consulting the backend."""
    snapdir = str(tmp_path)

    def fresh():
        db = IPDB(snapshot_dir=snapdir)
        db.register_table("T", Table.from_rows(
            [{"a": i, "txt": f"row {i}"} for i in range(24)]))
        pred = LatencyScriptedPredictor(scripted_answers,
                                        base_latency_s=0.25)
        register_scripted(db, "m", pred)
        db.set_option("chunk_size", 4)
        db.set_option("batch_size", 4)
        db.set_option("enable_pilot", False)
        return db, pred

    db1, pred1 = fresh()
    with db1, FrontDoor(db1, snapshot_every_s=0.1) as fd1:
        cli = FrontDoorClient(fd1.host, fd1.port)
        assert cli.query(q("persist")).result()["status"] == "ok"
        assert len(pred1.dispatch_log) > 0
        assert wait_for(
            lambda: cli.server_stats().get("snapshots", 0) >= 1)

    db2, pred2 = fresh()
    assert db2.restored_snapshot is not None
    with db2, FrontDoor(db2) as fd2:
        cli2 = FrontDoorClient(fd2.host, fd2.port)
        res = cli2.query(q("persist")).result()
        assert res["status"] == "ok" and res["rows"] == 24
    assert len(pred2.dispatch_log) == 0, \
        "warm-restored front door must serve from the snapshot"
