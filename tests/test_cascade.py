"""End-to-end and unit coverage for calibrated model cascades.

The contract under test (core/cascade.py):

  * per-answer confidence plumbing — oracle/tabular/scripted backends
    populate `CallResult.confidences` from the reserved "__confidence__"
    answer key; text-only backends degrade to all-1.0;
  * correctness — with a perfect proxy the cascade's rows are
    byte-identical to the direct route's; rows in the escalation band are
    resolved by the expensive backend, so a proxy that is wrong ONLY
    where it is unconfident still yields direct-route rows;
  * contracts — a proxy that cannot meet the declared precision target
    calibrates to `unachievable` and the optimizer routes the operator
    direct (zero proxy calls);
  * accounting — observed predicate selectivity under a cascade matches
    direct execution exactly (final verdicts, base key, recorded once:
    the stage-tag split in service.staged_key);
  * determinism — rows, ExecStats and EXPLAIN are bit-identical across
    dispatch_workers {1, 2, 4} (the PR 4 concurrency contract extends to
    two-stage routing).

Scripted backends keep every modeled latency an exact binary fraction so
float sums are order-independent; confidences and verdicts are pure
functions of the row text, so calibration snapshots and audit schedules
cannot depend on batch composition.
"""
import dataclasses
import json
import re

import pytest

from helpers import LatencyScriptedPredictor, register_scripted

from repro.core.cascade import CascadePredictor, confidences_of, row_hash
from repro.core.database import IPDB
from repro.core.executors import CallResult, OracleExecutor, TabularExecutor
from repro.core.service import staged_key
from repro.core.stats import StatisticsStore
from repro.relational.table import Table


# ---------------------------------------------------------------------------
# scripted task: flag(i) = i % 2 == 0, i recovered from the row text
# ---------------------------------------------------------------------------
def _i_of(row) -> int:
    try:
        return int(str(row.get("txt", "0")).split()[-1])
    except ValueError:
        return 0


def truth_answers(instruction, rows):
    return [{"flag": _i_of(r) % 2 == 0} for r in rows]


def perfect_proxy(instruction, rows):
    """Always right, uniformly confident."""
    return [{"flag": _i_of(r) % 2 == 0, "__confidence__": 0.9}
            for r in rows]


def wrong_proxy(instruction, rows):
    """Always wrong, confidently — no threshold can meet any contract."""
    return [{"flag": _i_of(r) % 2 != 0, "__confidence__": 0.9}
            for r in rows]


def banded_proxy(instruction, rows):
    """Wrong exactly where unconfident: every i % 4 == 0 row gets a
    flipped verdict at confidence 0.3, the rest are right at 0.95 — so a
    0.95-precision contract calibrates to tau = 0.95 and the low-band
    rows escalate."""
    out = []
    for r in rows:
        i = _i_of(r)
        if i % 4 == 0:
            out.append({"flag": i % 2 != 0, "__confidence__": 0.3})
        else:
            out.append({"flag": i % 2 == 0, "__confidence__": 0.95})
    return out


PROMPT = "keep {flag BOOLEAN} of {{txt}}"
WITH = "WITH (cascade_proxy=proxym, cascade_target_precision=0.95)"
# slice A (a < 24) warms the calibration reservoir; slice B (a >= 24) is
# disjoint, so measurement prompts never hit the cross-query PromptCache
Q_WARM = (f"SELECT a FROM T WHERE a < 24 AND "
          f"LLM bigm (PROMPT '{PROMPT}') {WITH} = TRUE")
Q_MEASURE = (f"SELECT a FROM T WHERE a >= 24 AND "
             f"LLM bigm (PROMPT '{PROMPT}') {WITH} = TRUE")
Q_DIRECT = (f"SELECT a FROM T WHERE a >= 24 AND "
            f"LLM bigm (PROMPT '{PROMPT}') = TRUE")


def make_db(proxy_fn, *, workers=1, n=48):
    db = IPDB()
    db.register_table("T", Table.from_rows(
        [{"a": i, "txt": f"row {i}"} for i in range(n)]))
    # exact binary-fraction latencies → order-independent float sums
    expensive = LatencyScriptedPredictor(truth_answers, base_latency_s=1.0)
    register_scripted(db, "bigm", expensive)
    if proxy_fn is not None:
        proxy = LatencyScriptedPredictor(proxy_fn, base_latency_s=0.0625)
        register_scripted(db, "proxym", proxy)
    db.set_option("dispatch_workers", workers)
    db.set_option("batch_size", 16)
    return db


_WORKERS_RE = re.compile(r"dispatch_workers=\d+")
_PCOUNT_RE = re.compile(r"__p_\d+_")


def _norm_explain(text: str) -> str:
    return _PCOUNT_RE.sub("__p_N_", _WORKERS_RE.sub("dispatch_workers=N",
                                                    text))


def _stats_dict(stats):
    d = dataclasses.asdict(stats)
    d.pop("wall_s")
    d.pop("queue_wait_s")
    return d


# ---------------------------------------------------------------------------
# satellite: per-answer confidence plumbing
# ---------------------------------------------------------------------------
def test_oracle_executor_populates_confidences():
    def oracle(instruction, rows):
        return [{"flag": True, "__confidence__": 0.7},
                {"flag": False, "__confidence__": 0.4}]

    ex = OracleExecutor(oracle)
    res = ex.complete("p", (("flag", "BOOLEAN"),), 3,
                      rows=[{"x": 1}, {"x": 2}, {"x": 3}], instruction="i")
    # two answered rows carry their scores; the padded third reads 0.0
    assert res.confidences == [0.7, 0.4, 0.0]
    # the reserved key never leaks into the serialized answer text
    assert "__confidence__" not in res.text
    objs = json.loads(res.text)
    assert [o["flag"] for o in objs] == [True, False, None]


def test_tabular_executor_populates_confidences():
    def predict(rows):
        return [{"y": r["x"] * 2, "__confidence__": 0.25 * r["x"]}
                for r in rows]

    ex = TabularExecutor(predict)
    res = ex.complete("", (("y", "INTEGER"),), 2,
                      rows=[{"x": 1}, {"x": 2}])
    assert res.confidences == [0.25, 0.5]
    many = ex.complete_many(["", ""], (("y", "INTEGER"),), [1, 2],
                            rows_list=[[{"x": 3}], [{"x": 1}, {"x": 2}]])
    assert many[0].confidences == [0.75]
    assert many[1].confidences == [0.25, 0.5]
    assert "__confidence__" not in many[1].text


def test_scripted_predictor_populates_confidences():
    ex = LatencyScriptedPredictor(perfect_proxy)
    res = ex.complete("p", (("flag", "BOOLEAN"),), 2,
                      rows=[{"txt": "row 1"}, {"txt": "row 2"}])
    assert res.confidences == [0.9, 0.9]


def test_confidences_of_text_only_fallback():
    # a backend with no score channel reads as uniformly confident
    assert confidences_of(CallResult("t", 1, 1, 0.0, 0.0), 3) == \
        [1.0, 1.0, 1.0]
    # short vectors pad with 0.0 (unanswered rows), long ones truncate
    r = CallResult("t", 1, 1, 0.0, 0.0, confidences=[0.5])
    assert confidences_of(r, 3) == [0.5, 0.0, 0.0]
    r = CallResult("t", 1, 1, 0.0, 0.0, confidences=[0.5, 0.6, 0.7])
    assert confidences_of(r, 2) == [0.5, 0.6]


def test_staged_key_tags_stage():
    assert staged_key(("m", "i"), "") == ("m", "i")
    assert staged_key(("m", "i"), "cascade") == ("m#cascade", "i")


# ---------------------------------------------------------------------------
# calibration unit tests (StatisticsStore.calibrate_cascade)
# ---------------------------------------------------------------------------
KEY = ("m", "instr")


def test_calibrate_cold_below_min_records():
    store = StatisticsStore()
    for h in range(5):
        store.record_cascade_agreement(KEY, h, 0.9, True, True)
    cal = store.calibrate_cascade(KEY, 0.9, min_records=8)
    assert cal.status == "cold"
    assert cal.tau_pos > 1.0 and cal.tau_neg > 1.0
    assert cal.escalation_rate == 1.0


def test_calibrate_ok_thresholds_maximize_coverage():
    store = StatisticsStore()
    # positive class: 10 agreeing records at 0.9, 2 disagreeing at 0.3 —
    # at target 0.95 any prefix reaching into the 0.3 records fails
    # (10/11 < 0.95), so tau_pos settles at 0.9
    for h in range(10):
        store.record_cascade_agreement(KEY, h, 0.9, True, True)
    for h in range(10, 12):
        store.record_cascade_agreement(KEY, h, 0.3, True, False)
    # negative class all agree at 0.6: tau_neg accepts everything
    for h in range(12, 20):
        store.record_cascade_agreement(KEY, h, 0.6, False, True)
    cal = store.calibrate_cascade(KEY, 0.95, min_records=8)
    assert cal.status == "ok"
    assert cal.tau_pos == pytest.approx(0.9)
    assert cal.tau_neg == pytest.approx(0.6)
    assert 0.0 <= cal.escalation_rate < 1.0
    assert cal.empirical_precision == pytest.approx(1.0)


def test_calibrate_unachievable_when_proxy_never_agrees():
    store = StatisticsStore()
    for h in range(20):
        store.record_cascade_agreement(KEY, h, 0.9, h % 2 == 0, False)
    cal = store.calibrate_cascade(KEY, 0.9, min_records=8)
    assert cal.status == "unachievable"
    assert cal.tau_pos > 1.0 and cal.tau_neg > 1.0
    assert cal.escalation_rate == 1.0


def test_calibrate_violated_by_failing_audits():
    store = StatisticsStore()
    for h in range(30):
        store.record_cascade_agreement(KEY, h, 0.9, True, True)
    # 16 audited acceptances all disagreed: the contract is broken even
    # though the (low-confidence) reservoir slice still calibrates
    for h in range(30, 46):
        store.record_cascade_agreement(KEY, h, 0.2, True, False,
                                       audited=True)
    cal = store.calibrate_cascade(KEY, 0.9, min_records=8)
    assert cal.status == "violated"
    assert cal.empirical_precision == pytest.approx(0.0)


def test_reservoir_eviction_keeps_smallest_hashes():
    store = StatisticsStore()
    for h in range(300):
        store.record_cascade_agreement(KEY, h, 0.5, True, True)
    rec = store.cascade_get(KEY)
    assert rec.n_records == 256
    assert max(rec.reservoir) == 255    # keep-smallest is order-free


# ---------------------------------------------------------------------------
# e2e: perfect proxy — byte-identical rows, expensive stage mostly idle
# ---------------------------------------------------------------------------
def test_perfect_proxy_rows_match_direct():
    direct_db = make_db(None)
    direct_rows = direct_db.sql(Q_DIRECT).table.rows()
    direct_db.close()

    db = make_db(perfect_proxy)
    warm = db.sql(Q_WARM)
    # cold calibration escalates everything: the bootstrap pays full
    # direct cost but buys the held-out evidence
    assert warm.stats.proxy_calls > 0
    assert warm.stats.escalated_rows == warm.stats.cascade_rows > 0

    r = db.sql(Q_MEASURE)
    assert r.table.rows() == direct_rows
    # calibrated route: the proxy resolves (nearly) everything — only
    # deterministic 1-in-16 audits still reach the expensive backend
    assert r.stats.proxy_calls > 0
    assert r.stats.cascade_rows == 24
    assert r.stats.escalated_rows < r.stats.cascade_rows / 2
    db.close()


def test_escalation_band_resolved_by_expensive_backend():
    direct_db = make_db(None)
    direct_rows = direct_db.sql(Q_DIRECT).table.rows()
    direct_db.close()

    db = make_db(banded_proxy)
    db.sql(Q_WARM)
    r = db.sql(Q_MEASURE)
    # the proxy is WRONG on every i % 4 == 0 row — but only at
    # confidence 0.3, below tau: those rows escalate and the expensive
    # backend's verdicts splice in, so the output still matches direct
    assert r.table.rows() == direct_rows
    assert r.stats.escalated_rows >= 6          # the 0.3-confidence band
    assert r.stats.escalated_rows < r.stats.cascade_rows
    assert r.stats.escalated_calls < r.stats.proxy_calls + 1
    db.close()


def test_unachievable_contract_routes_direct():
    direct_db = make_db(None)
    direct_rows = direct_db.sql(Q_DIRECT).table.rows()
    direct_db.close()

    db = make_db(wrong_proxy)
    warm = db.sql(Q_WARM)                       # records 100% disagreement
    assert warm.stats.escalated_rows == warm.stats.cascade_rows
    explain = db.explain(Q_MEASURE)
    assert "route=direct" in explain
    assert "status=unachievable" in explain
    r = db.sql(Q_MEASURE)
    # the optimizer fell back to the direct route: zero proxy calls, and
    # a confidently-wrong proxy cannot corrupt a single row
    assert r.stats.proxy_calls == 0
    assert r.stats.escalated_calls == 0
    assert r.table.rows() == direct_rows
    db.close()


# ---------------------------------------------------------------------------
# satellite: selectivity recorded once, matching direct execution
# ---------------------------------------------------------------------------
def test_cascade_selectivity_matches_direct():
    def observed(db):
        key = next(k for k in db.stats_store.keys() if k[0] == "bigm")
        rec = db.stats_store.get(key)
        return key, (rec.rows_in, rec.rows_passed)

    direct_db = make_db(None)
    direct_db.sql(Q_DIRECT.replace("a >= 24", "a < 24"))
    direct_db.sql(Q_DIRECT)
    key, direct_obs = observed(direct_db)
    direct_db.close()

    db = make_db(perfect_proxy)
    db.sql(Q_WARM)
    db.sql(Q_MEASURE)
    _, cascade_obs = observed(db)
    # final verdicts recorded exactly once on the BASE key: warm-cache
    # selectivity is indistinguishable from direct execution
    assert cascade_obs == direct_obs
    # the stage-tagged key carries call accounting only — never
    # predicate rows (that would double-count selectivity)
    tagged = db.stats_store.get(staged_key(key, "cascade"))
    assert tagged is not None and tagged.calls > 0
    assert (tagged.rows_in, tagged.rows_passed) == (0, 0)
    # proxy-stage calls land under the proxy's own key, where the cost
    # model's cascade estimate observes them
    prox = db.stats_store.get(("proxym", key[1]))
    assert prox is not None and prox.calls > 0
    db.close()


# ---------------------------------------------------------------------------
# e2e: EXPLAIN -- cascade -- section
# ---------------------------------------------------------------------------
def test_explain_shows_cascade_section():
    db = make_db(banded_proxy)
    cold = db.explain(Q_MEASURE)
    assert "-- cascade --" in cold
    assert "status=cold" in cold and "route=cascade" in cold
    assert "accept_pos>=" in cold and "accept_neg>=" in cold

    db.sql(Q_WARM)
    warm = db.explain(Q_MEASURE)
    assert "status=ok" in warm
    assert "target_precision=0.950" in warm
    assert "accept_pos>=0.950" in warm and "accept_neg>=0.950" in warm
    assert "est_rate=0.250" in warm             # the i % 4 == 0 band
    assert re.search(r"observed=rows=\d+/\d+", warm)
    db.close()


def test_explain_direct_query_reports_no_cascade():
    db = make_db(None)
    explain = db.explain(Q_DIRECT)
    assert "-- cascade --" in explain
    assert "(no cascaded operators)" in explain
    db.close()


# ---------------------------------------------------------------------------
# determinism: bit-identical rows/stats/EXPLAIN across dispatch workers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("proxy_fn", [perfect_proxy, banded_proxy],
                         ids=["perfect", "banded"])
def test_bit_identical_across_dispatch_workers(proxy_fn):
    reference = None
    for workers in (1, 2, 4):
        db = make_db(proxy_fn, workers=workers)
        db.sql(Q_WARM)
        explain = _norm_explain(db.explain(Q_MEASURE))
        r = db.sql(Q_MEASURE)
        db.close()
        entry = (r.table.rows(), _stats_dict(r.stats), explain)
        if reference is None:
            reference = entry
        assert entry == reference, f"diverged at workers={workers}"
    # sanity: the reference actually exercised the cascade
    assert reference[1]["proxy_calls"] > 0


# ---------------------------------------------------------------------------
# predictor-level: re-marshaled escalation batches
# ---------------------------------------------------------------------------
def test_cascade_predictor_remarshals_escalations():
    """Escalated rows from several prompts re-batch into full
    `batch_size`-row expensive prompts instead of per-row dribble."""
    store = StatisticsStore()
    key = ("big", "keep flag of <txt>")
    # warm the reservoir so low-confidence rows form an escalation band
    for h in range(8):
        store.record_cascade_agreement(key, h, 0.95, True, True)
    for h in range(8, 16):
        store.record_cascade_agreement(key, h, 0.95, False, True)

    proxy = LatencyScriptedPredictor(banded_proxy, base_latency_s=0.0625)
    expensive = LatencyScriptedPredictor(truth_answers, base_latency_s=1.0)
    casc = CascadePredictor(proxy, expensive, store=store, key=key,
                            proxy_model="small", target_precision=0.95,
                            audit_every=0)
    casc.configure({"batch_size": 4, "use_batching": True})
    casc.load()
    assert casc.calibration.status == "ok"

    from repro.core.predict import render_rows
    schema = (("flag", "BOOLEAN"),)
    pre = "keep flag of <txt>\n"
    groups = [[{"txt": f"row {i}"} for i in range(s, s + 4)]
              for s in (0, 4, 8)]                # 3 prompts x 4 rows
    prompts = [pre + render_rows(g) for g in groups]
    res = casc.complete_many(prompts, schema, [4, 4, 4], rows_list=groups,
                             instruction="keep flag of <txt>")
    # i % 4 == 0 rows (0, 4, 8) escalate: ONE re-marshaled 3-row prompt
    # in ONE expensive dispatch, not three single-row dribbles
    assert [b for _, b in expensive.dispatch_log] == [1]
    merged = [obj for r, g in zip(res, groups)
              for obj in json.loads(r.text)]
    assert [o["flag"] for o in merged] == \
        [_i_of(r) % 2 == 0 for g in groups for r in g]
    assert res[0].proxy_calls == 3
    assert res[0].escalated_calls == 1
    assert res[0].cascade_rows == 12 and res[0].escalated_rows == 3
    # hash-keyed agreement reservoir grew by the three escalated rows
    assert store.cascade_get(key).n_records == 16 + 3


def test_row_hash_is_content_keyed():
    a = row_hash("instr", {"txt": "row 1"})
    assert a == row_hash("instr", {"txt": "row 1"})
    assert a != row_hash("instr", {"txt": "row 2"})
    assert a != row_hash("other", {"txt": "row 1"})
