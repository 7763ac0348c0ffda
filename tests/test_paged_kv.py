"""Paged KV cache: allocator bookkeeping, prefix-memo LRU, paged-vs-dense
equivalence across the slot/prefix/raggedness grid, pool bounds, and the
query-layer stats surfacing.

Cross-layout equality tests run the smoke model with float32 compute:
dense and paged attention are mathematically identical but travel
different reduction paths, and bfloat16's coarse rounding would turn the
byte-equality assertions into near-tie coin tosses.
"""
import json

import numpy as np
import pytest

import repro.configs as C
from repro.core.database import IPDB
from repro.core.executors import JaxExecutor
from repro.relational.table import Table
from repro.serving.engine import GenStats, InferenceEngine, PageAllocator
from repro.serving.grammar import Field, JsonGrammar
from repro.serving.scheduler import ContinuousBatcher, Request

PREFIX = "SHARED INSTRUCTION BLOCK: extract the field from the row. " * 3


def _cfg():
    return C.get_smoke_config("olmo-1b").replace(vocab_size=259,
                                                 compute_dtype="float32")


def _engine(layout, **kw):
    kw.setdefault("max_len", 512)
    kw.setdefault("seed", 0)
    return InferenceEngine(_cfg(), kv_layout=layout, page_size=32, **kw)


def _assert_refcount_baseline(eng):
    """After a run completes, the only live page references are cache
    residencies (prefix-memo entries and/or radix-tree nodes) with exactly
    one reference each — anything else is a leaked slot/prefix lease."""
    if eng._alloc is None:
        return
    resident = [p for e in eng._prefix_kv.values()
                if e.pages is not None for p in e.pages]
    if eng._radix is not None:
        resident += eng._radix.resident_page_ids()
    assert eng._alloc.in_use == len(resident), \
        (eng._alloc.in_use, len(resident))
    assert all(eng._alloc.refs(p) == 1 for p in resident)


# ------------------------------ page allocator --------------------------------
def test_page_allocator_alloc_free_refcount():
    a = PageAllocator(6)
    p1 = a.alloc(2)
    p2 = a.alloc(3)
    assert a.in_use == 5 and a.free_pages == 1
    assert a.peak_in_use == 5
    # introspection aliases surfaced by EXPLAIN's pool line
    assert a.resident_pages == a.in_use == 5
    assert a.high_water == a.peak_in_use == 5
    a.retain(p1)                 # second reference (shared prefix)
    a.release(p1)
    assert a.in_use == 5         # still referenced
    a.release(p1)
    assert a.in_use == 3         # now freed
    a.release(p2)
    assert a.in_use == 0 and a.free_pages == 6
    assert a.peak_in_use == 5    # high-water survives frees
    with pytest.raises(RuntimeError):
        a.alloc(7)
    a.grow(4)
    assert a.free_pages == 10
    assert len(set(a.alloc(10))) == 10


def test_page_allocator_double_free_asserts():
    a = PageAllocator(2)
    p = a.alloc(1)
    a.release(p)
    with pytest.raises(AssertionError):
        a.release(p)


# ------------------------------ prefix memo LRU -------------------------------
def test_prefix_memo_lru_cap_and_touch_on_get():
    eng = _engine("dense", prefix_memo_entries=2)
    g = JsonGrammar([Field("x", "BOOLEAN")])

    def gen(prefix):
        return eng.generate(["row a"], grammar=g, shared_prefix=prefix,
                            max_new_tokens=24)

    gen("prefix one ")
    gen("prefix two ")
    assert len(eng._prefix_kv) == 2
    # touch "one" (hit), then insert a third: "two" must be the evictee
    r = gen("prefix one ")
    assert r.stats.prefix_hits == 1
    gen("prefix three ")
    assert len(eng._prefix_kv) == 2
    keys = [k[0] for k in eng._prefix_kv]
    assert "prefix one " in keys and "prefix three " in keys
    # the untouched entry was evicted: using it again is a miss
    r2 = gen("prefix two ")
    assert r2.stats.prefix_hits == 0 and r2.stats.prefill_tokens > 0


def test_prefix_memo_eviction_releases_pages():
    # exact mode: this test pins the PR-5 whole-string memo semantics
    eng = _engine("paged", prefix_memo_entries=1, prefix_cache_mode="exact")
    g = JsonGrammar([Field("x", "BOOLEAN")])
    eng.generate(["row"], grammar=g, shared_prefix=PREFIX, max_new_tokens=16)
    resident = eng._alloc.in_use
    assert resident > 0          # prefix pages stay resident for reuse
    eng.generate(["row"], grammar=g, shared_prefix=PREFIX * 2,
                 max_new_tokens=16)
    # cap=1: the first prefix's residency was dropped when the second came in
    ents = list(eng._prefix_kv.values())
    assert len(ents) == 1
    assert eng._alloc.in_use == len(ents[0].pages)


# --------------------------- generate equivalence -----------------------------
def test_generate_paged_matches_dense_and_monolithic():
    d, p = _engine("dense"), _engine("paged")
    g = JsonGrammar([Field("x", "INTEGER")])
    rows = [f"row: item{i}{i}" for i in range(4)]
    rd = d.generate(rows, grammar=g, shared_prefix=PREFIX, max_new_tokens=48)
    rp = p.generate(rows, grammar=g, shared_prefix=PREFIX, max_new_tokens=48)
    mono = d.generate([PREFIX + r for r in rows], grammar=g,
                      max_new_tokens=48)
    assert rd.texts == rp.texts == mono.texts
    assert (rd.stats.input_tokens, rd.stats.output_tokens) == \
        (rp.stats.input_tokens, rp.stats.output_tokens)
    assert 0 < rp.stats.kv_bytes < rd.stats.kv_bytes
    # second paged call: prefix answered from resident pages, no re-prefill
    rp2 = p.generate(rows, grammar=g, shared_prefix=PREFIX, max_new_tokens=48)
    assert rp2.texts == rp.texts
    assert rp2.stats.prefix_hits == 1
    assert rp2.stats.prefill_tokens < rp.stats.prefill_tokens


# ------------------------ batcher equivalence grid ----------------------------
def _ragged_prompts(n):
    return [f"row {i}: " + ("detail " * (i % 5)) + f"value {i * 7}"
            for i in range(n)]


@pytest.mark.parametrize("num_slots", [2, 8])
@pytest.mark.parametrize("with_prefix", [False, True])
def test_batcher_paged_matches_dense(num_slots, with_prefix):
    """Identical decoded text + token accounting across layouts for
    slots {2,8} × prefix {none,long} × ragged request lengths; with a
    prefix the paged layout must do strictly less prefill work."""
    prefix = PREFIX if with_prefix else ""
    prompts = _ragged_prompts(7)

    def reqs():
        return [Request(prompt=p, grammar=JsonGrammar([Field("v", "INTEGER")]),
                        max_new_tokens=64) for p in prompts]

    d, p = _engine("dense"), _engine("paged")
    cbd = ContinuousBatcher(d, num_slots=num_slots)
    cbp = ContinuousBatcher(p, num_slots=num_slots)
    done_d = cbd.run(reqs(), shared_prefix=prefix)   # dense: prepends
    done_p = cbp.run(reqs(), shared_prefix=prefix)   # paged: shares pages
    assert [r.text for r in done_d] == [r.text for r in done_p]
    assert [r.rid for r in done_p] == list(range(len(prompts)))
    sd, sp = cbd.stats, cbp.stats
    assert (sd.input_tokens, sd.output_tokens, sd.decode_steps) == \
        (sp.input_tokens, sp.output_tokens, sp.decode_steps)
    assert 0 < sp.kv_bytes < sd.kv_bytes
    if with_prefix:
        assert sp.prefill_tokens < sd.prefill_tokens
    # paged run must leave no leaked pages (cache residency only)
    _assert_refcount_baseline(p)


def test_batcher_paged_token_budget_eviction_frees_pages():
    eng = _engine("paged")
    g = JsonGrammar([Field("s", "VARCHAR")], max_str=8)
    reqs = [Request(prompt=f"word {i}", grammar=g, max_new_tokens=48)
            for i in range(4)]
    reqs[1].max_new_tokens = 2         # cannot finish the JSON grammar
    cb = ContinuousBatcher(eng, num_slots=2)
    done = cb.run(reqs)
    assert done[1].error and "budget" in done[1].error
    for i in (0, 2, 3):
        assert done[i].error is None
        json.loads(done[i].text)
    # eviction freed the slot's pages (prompts are sub-page: nothing is
    # committed to the radix tree, so the pool must drain to empty)
    assert eng._alloc.in_use == 0
    _assert_refcount_baseline(eng)


def test_paged_pool_bound_stalls_but_completes():
    """A pinned page pool smaller than num_slots×max_len still completes
    every request: refills stall until other slots free pages."""
    # 512-token rows at ps=32 → 16 blocks/row worst case; give ~2 rows
    eng = _engine("paged", page_pool_pages=16)
    g = JsonGrammar([Field("v", "INTEGER")])
    reqs = [Request(prompt=f"n {i}", grammar=g, max_new_tokens=32)
            for i in range(6)]
    cb = ContinuousBatcher(eng, num_slots=4)
    done = cb.run(reqs)
    assert all(r.text is not None for r in done)
    assert eng._alloc.num_pages == 16  # pinned: never grew
    _assert_refcount_baseline(eng)
    # same requests through an unbounded engine decode identically
    ref = ContinuousBatcher(_engine("paged"), num_slots=4).run(
        [Request(prompt=f"n {i}", grammar=g, max_new_tokens=32)
         for i in range(6)])
    assert [r.text for r in done] == [r.text for r in ref]


def test_paged_pallas_decode_matches_jnp():
    """End-to-end check of decode_attention_paged_pallas inside the model
    (interpret mode on CPU)."""
    base = _engine("paged", max_len=128)
    kern = _engine("paged", max_len=128, use_pallas_decode=True)
    g = JsonGrammar([Field("x", "BOOLEAN")])
    prompts = ["row alpha", "row beta"]
    r1 = base.generate(prompts, grammar=g, max_new_tokens=16)
    r2 = kern.generate(prompts, grammar=g, max_new_tokens=16)
    assert r1.texts == r2.texts


# --------------------------- executor + SQL layer -----------------------------
def test_jax_executor_paged_common_prefix_split():
    prompts = [PREFIX + f"row {i}: value {i}" for i in range(5)]
    outs = {}
    for layout in ("dense", "paged"):
        ex = JaxExecutor(_engine(layout))
        ex.configure({"num_slots": 4, "temperature": 0.0, "max_tokens": 64})
        res = ex.complete_many(prompts, [("v", "INTEGER")], [1] * 5)
        outs[layout] = [r.text for r in res]
        if layout == "paged":
            assert sum(r.prefill_tokens for r in res) > 0
            _assert_refcount_baseline(ex.engine)
    assert outs["dense"] == outs["paged"]


def test_jax_executor_paged_explicit_shared_prefix():
    """Service contract: prompts are suffixes EXCLUDING shared_prefix —
    the paged batcher route must not strip the prefix from them again."""
    suffixes = [f"row {i}: value {i}" for i in range(4)]
    outs = {}
    for layout in ("dense", "paged"):
        ex = JaxExecutor(_engine(layout))
        ex.configure({"num_slots": 4, "temperature": 0.0, "max_tokens": 64})
        res = ex.complete_many(suffixes, [("v", "INTEGER")], [1] * 4,
                               shared_prefix=PREFIX)
        outs[layout] = [(r.text, r.in_tokens) for r in res]
        if layout == "paged":
            _assert_refcount_baseline(ex.engine)
    assert outs["dense"] == outs["paged"]


def _sql_db(layout):
    db = IPDB()
    db.register_table("Items", Table.from_rows(
        [{"name": f"item {i}"} for i in range(6)]))
    eng = _engine(layout)

    def factory(entry):
        ex = JaxExecutor(eng)
        ex.configure(dict(entry.options))
        return ex

    db.register_executor("t_jax", factory)
    db.sql("CREATE LLM MODEL anno PATH 'custom:t_jax' ON PROMPT "
           "OPTIONS { 'batch_size': 1, 'max_str': 6, 'temperature': 0.0, "
           "'num_slots': 4, 'max_tokens': 48 }")
    db.set_option("batch_size", 1)
    db.set_option("max_dispatch_calls", 3)    # ≥2 dispatches per query
    return db, eng


def test_execstats_surface_prefill_decode_prefix():
    q = ("SELECT name, LLM anno (PROMPT '" + PREFIX +
         "guess the {color VARCHAR} of {{name}}') AS color FROM Items")
    rows = {}
    stats = {}
    for layout in ("dense", "paged"):
        db, eng = _sql_db(layout)
        r = db.sql(q)
        rows[layout] = r.table.rows()
        stats[layout] = r.stats
        db.close()
    assert rows["dense"] == rows["paged"]
    for layout in ("dense", "paged"):
        s = stats[layout]
        assert s.prefill_tokens > 0 and s.decode_tokens > 0
    # ≥2 dispatch batches share one instruction: the later ones hit the memo
    assert stats["paged"].prefix_hits >= 1
    assert stats["paged"].prefill_tokens < stats["dense"].prefill_tokens


def test_explain_dispatch_shows_kv_layout():
    db = IPDB()
    db.register_table("Items", Table.from_rows([{"name": "x"}]))
    db.register_oracle("o", lambda instr, rows: [{"c": "red"} for _ in rows])
    db.sql("CREATE LLM MODEL m PATH 'oracle:o' ON PROMPT")
    db.set_option("kv_layout", "paged")
    out = db.explain("SELECT name, LLM m (PROMPT 'get {c VARCHAR} of "
                     "{{name}}') AS c FROM Items")
    assert "-- dispatch --" in out
    assert "kv_layout=paged" in out
    assert "prefix_hits=" in out and "prefill_tokens=" in out
    assert "radix_hit_tokens=" in out and "kv_quant=" in out
    assert "pool: 0/0 pages, hwm=0" in out   # oracle backend: no jax pool
    assert "param_bytes=0" in out            # ... and no jax weights
    db.close()


def test_genstats_add_kv_bytes_is_high_water():
    a = GenStats(kv_bytes=100, prefill_tokens=5)
    b = GenStats(kv_bytes=40, prefill_tokens=7)
    a.add(b)
    assert a.kv_bytes == 100 and a.prefill_tokens == 12
    b.add(GenStats(kv_bytes=90))
    assert b.kv_bytes == 90
