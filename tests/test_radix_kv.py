"""Radix prefix KV cache: tree invariants (refcounts, orphans, LRU safety),
copy-on-write sample forks, int8 frozen-page quantization, the token-boundary
prefix carve, and the n_samples SQL surface.

Invariant property tests run as seeded random trajectories so they always
execute; when hypothesis is installed the same checker is additionally driven
by @given-generated operation sequences.
"""
import json

import numpy as np
import pytest

import repro.configs as C
from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core.database import IPDB
from repro.core.executors import JaxExecutor
from repro.relational.table import Table
from repro.serving.engine import InferenceEngine, PageAllocator
from repro.serving.grammar import Field, JsonGrammar
from repro.serving.radix import RadixPrefixCache
from repro.serving.scheduler import ContinuousBatcher, Request

PREFIX = "SHARED INSTRUCTION BLOCK: extract the field from the row. " * 3
PS = 4          # tiny pages make radix splits/partial matches common


def _cfg():
    return C.get_smoke_config("olmo-1b").replace(vocab_size=259,
                                                 compute_dtype="float32")


def _engine(**kw):
    kw.setdefault("max_len", 512)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 32)
    return InferenceEngine(_cfg(), kv_layout="paged", **kw)


# ------------------------------ tree unit tests -------------------------------
def _tree(pages=64):
    a = PageAllocator(pages)
    return RadixPrefixCache(a, PS), a


def _commit(tree, alloc, tokens):
    """Engine-style commit: alloc a lease, insert, drop the lease — the
    tree keeps exactly one reference per adopted page."""
    nfull = len(tokens) // PS
    pg = alloc.alloc(nfull)
    tree.insert(list(tokens[:nfull * PS]), pg)
    alloc.release(pg)
    return pg


def test_radix_insert_match_roundtrip():
    tree, a = _tree()
    toks = list(range(10))                     # 2 full pages + tail of 2
    _commit(tree, a, toks)
    assert tree.resident_pages == 2 and a.in_use == 2
    pages, n = tree.match(toks)
    assert n == 8 and len(pages) == 2          # capped at last full page
    assert all(a.refs(p) == 2 for p in pages)  # retained for the caller
    a.release(pages)
    assert all(a.refs(p) == 1 for p in pages)


def test_radix_partial_overlap_inside_node():
    tree, a = _tree()
    _commit(tree, a, [1, 2, 3, 4, 5, 6, 7, 8])         # one 2-page node
    pages, n = tree.match([1, 2, 3, 4, 9, 9, 9, 9, 0])  # page 1 diverges
    assert n == PS and len(pages) == 1
    a.release(pages)


def test_radix_split_preserves_single_reference():
    tree, a = _tree()
    _commit(tree, a, [1, 2, 3, 4, 5, 5, 5, 5])
    _commit(tree, a, [1, 2, 3, 4, 6, 6, 6, 6])   # splits the 2-page node
    ids = tree.resident_page_ids()
    assert len(ids) == len(set(ids)) == 3        # shared first page + 2 tails
    assert a.in_use == 3
    assert all(a.refs(p) == 1 for p in ids)
    for suffix, want in (([5, 5, 5, 5], 8), ([6, 6, 6, 6], 8)):
        pages, n = tree.match([1, 2, 3, 4] + suffix + [0])
        assert n == want and len(pages) == 2
        a.release(pages)


def test_radix_evict_lru_skips_live_readers():
    tree, a = _tree()
    _commit(tree, a, [1] * PS)
    _commit(tree, a, [2] * PS)
    held, n = tree.match([1] * PS + [0])       # outside reader on node 1
    assert n == PS
    freed = tree.evict(2)
    assert freed == 1                          # only node 2 was evictable
    pages, n = tree.match([1] * PS + [0])
    assert n == PS                             # live-reader node survived
    a.release(pages)
    a.release(held)
    assert tree.evict(1) == 1                  # now it can go
    assert a.in_use == 0 and tree.resident_pages == 0


def test_radix_clear_releases_everything():
    tree, a = _tree()
    _commit(tree, a, [1, 1, 1, 1, 2, 2, 2, 2])
    _commit(tree, a, [1, 1, 1, 1, 3, 3, 3, 3])
    assert a.in_use == 3
    tree.clear()
    assert a.in_use == 0 and tree.num_nodes == 0


# --------------------------- invariant trajectories ---------------------------
def _check_invariants(tree, alloc, outstanding):
    """Core radix/allocator invariants after any operation:
    * the tree owns each resident page exactly once (no duplicates),
    * every resident page carries the tree's reference plus any live
      match leases — never less (no orphans, no double frees),
    * total pool usage is exactly tree pages + match-held pages."""
    ids = tree.resident_page_ids()
    assert len(ids) == len(set(ids)), "page owned by two nodes"
    held = {}
    for pages in outstanding:
        for p in pages:
            held[p] = held.get(p, 0) + 1
    for p in ids:
        assert alloc.refs(p) == 1 + held.get(p, 0)
    extra = [p for p in held if p not in ids]
    # matched-then-evicted pages keep only their lease references
    for p in extra:
        assert alloc.refs(p) == held[p]
    assert alloc.in_use == len(ids) + len(extra)


def _run_trajectory(ops):
    """ops: sequence of (kind, seq_idx) with kind ∈ {0: insert, 1: match,
    2: release-oldest-match, 3: evict}. Token sequences come from a tiny
    alphabet so prefixes collide and splits happen."""
    rng = np.random.default_rng(1234)
    seqs = [list(rng.integers(0, 3, size=int(rng.integers(PS, 6 * PS))))
            for _ in range(8)]
    tree, a = _tree(pages=4096)
    outstanding = []
    for kind, i in ops:
        seq = seqs[i % len(seqs)]
        if kind == 0:
            _commit(tree, a, seq)
        elif kind == 1:
            pages, n = tree.match(seq)
            assert n % PS == 0 and len(pages) == n // PS
            if pages:
                outstanding.append(pages)
        elif kind == 2 and outstanding:
            a.release(outstanding.pop(0))
        elif kind == 3:
            before = {p for pages in outstanding for p in pages}
            tree.evict(2)
            # LRU must never drop a node with live outside readers
            assert before <= set(tree.resident_page_ids()) | before
            for p in before:
                assert a.refs(p) >= 1
        _check_invariants(tree, a, outstanding)
    for pages in outstanding:
        a.release(pages)
    tree.clear()
    assert a.in_use == 0


def test_radix_invariants_random_trajectory():
    rng = np.random.default_rng(7)
    ops = [(int(rng.integers(0, 4)), int(rng.integers(0, 8)))
           for _ in range(300)]
    _run_trajectory(ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 7)),
                    max_size=60))
    def test_radix_invariants_property(ops):
        _run_trajectory(ops)


# ------------------------- generate equivalence grid --------------------------
@pytest.mark.parametrize("with_prefix", [False, True])
def test_generate_radix_matches_dense_grid(with_prefix):
    """PR-5 float32 grid, radix edition: byte-identical rows vs the dense
    layout, and a second run that reuses the tree with strictly less
    prefill."""
    prefix = PREFIX if with_prefix else ""
    d = InferenceEngine(_cfg(), seed=0, max_len=512)
    p = _engine()
    g = JsonGrammar([Field("x", "INTEGER")])
    rows = [f"row {i}: " + ("detail " * (i % 4)) + f"value {i * 7}"
            for i in range(4)]
    rd = d.generate(rows, grammar=g, shared_prefix=prefix, max_new_tokens=48)
    rp = p.generate(rows, grammar=g, shared_prefix=prefix, max_new_tokens=48)
    assert rd.texts == rp.texts
    rp2 = p.generate(rows, grammar=g, shared_prefix=prefix, max_new_tokens=48)
    assert rp2.texts == rd.texts
    if with_prefix:      # generate() matches the batch-common prefix; the
        # prefixless rows share under a page of it, so nothing to reuse
        assert rp2.stats.radix_hit_tokens > 0
        assert rp2.stats.prefill_tokens < rp.stats.prefill_tokens
    # partial overlap: unseen suffixes still reuse the common prefix pages
    rows2 = [r + " extended" for r in rows]
    rd3 = d.generate(rows2, grammar=g, shared_prefix=prefix,
                     max_new_tokens=48)
    rp3 = p.generate(rows2, grammar=g, shared_prefix=prefix,
                     max_new_tokens=48)
    assert rd3.texts == rp3.texts
    if with_prefix:
        assert rp3.stats.radix_hit_tokens > 0


def test_batcher_radix_partial_overlap_reuse():
    """No caller-provided shared prefix at all: prompts that merely START
    alike still share pages through the tree (exact-string memo cannot)."""
    g = JsonGrammar([Field("v", "INTEGER")])
    mk = lambda: [Request(prompt=PREFIX + f"row {i}: value {i}", grammar=g,
                          max_new_tokens=32) for i in range(5)]
    d = InferenceEngine(_cfg(), seed=0, max_len=512)
    done_d = ContinuousBatcher(d, num_slots=4).run(mk())
    p = _engine()
    cb = ContinuousBatcher(p, num_slots=4)
    done_p = cb.run(mk())
    assert [r.text for r in done_d] == [r.text for r in done_p]
    assert cb.stats.radix_hit_tokens > 0       # later fills hit earlier pages
    # the exact-string memo gets NO reuse here (no caller-provided prefix):
    # radix prefill must be strictly below the exact engine's
    e = _engine(prefix_cache_mode="exact")
    cbe = ContinuousBatcher(e, num_slots=4)
    cbe.run(mk())
    assert cb.stats.prefill_tokens < cbe.stats.prefill_tokens
    tree = p._radix.resident_page_ids()
    assert p._alloc.in_use == len(tree)
    assert all(p._alloc.refs(x) == 1 for x in tree)


# ------------------------------ COW sample forks ------------------------------
def test_fork_samples_cow_and_majority_vote():
    base = _engine()
    cb0 = ContinuousBatcher(base, num_slots=4)
    single = cb0.run([Request(PREFIX + "classify the row", max_new_tokens=16,
                              grammar=JsonGrammar([Field("x", "BOOLEAN")]))])
    eng = _engine()
    cb = ContinuousBatcher(eng, num_slots=4)
    done = cb.run([Request(PREFIX + "classify the row", max_new_tokens=16,
                           grammar=JsonGrammar([Field("x", "BOOLEAN")]),
                           n_samples=3)])
    r = done[0]
    # greedy decoding: every forked stream is byte-identical to the
    # unforked run, so the vote is unanimous
    assert r.samples == [single[0].text] * 3
    assert r.text == single[0].text
    assert cb.stats.cow_copies > 0             # tail page privatized on write
    tree = eng._radix.resident_page_ids()
    assert eng._alloc.in_use == len(tree)
    assert all(eng._alloc.refs(x) == 1 for x in tree)
    # fork shares the prompt: far less prefill than 3 independent streams
    assert cb.stats.prefill_tokens < 2 * cb0.stats.prefill_tokens


def test_fork_sampling_votes_majority():
    eng = _engine()
    cb = ContinuousBatcher(eng, num_slots=4)
    done = cb.run([Request(PREFIX + "pick a value", max_new_tokens=8,
                           n_samples=4)], temperature=1.0)
    r = done[0]
    assert len(r.samples) == 4
    assert r.text in r.samples
    counts = {t: r.samples.count(t) for t in set(r.samples)}
    assert counts[r.text] == max(counts.values())


# ------------------------------- int8 pages -----------------------------------
def test_int8_quantize_on_commit_cuts_kv_bytes():
    g = JsonGrammar([Field("x", "INTEGER")])
    rows = [f"row {i}: value {i * 3}" for i in range(3)]
    f32 = _engine()
    r1 = f32.generate(rows, grammar=g, shared_prefix=PREFIX,
                      max_new_tokens=32)
    q8 = _engine(kv_quant="int8")
    q1 = q8.generate(rows, grammar=g, shared_prefix=PREFIX, max_new_tokens=32)
    # first run reads fp pages (freezing happens at commit, after prefill):
    # byte-identical to the unquantized engine
    assert q1.texts == r1.texts
    assert int(np.sum(q8._quant_flags > 0)) > 0    # pages froze on commit
    # second run reads the int8 shadows: bounded drift — grammar-valid JSON
    # with the same schema, and a strictly lower logical KV footprint
    q2 = q8.generate(rows, grammar=g, shared_prefix=PREFIX, max_new_tokens=32)
    assert q2.stats.radix_hit_tokens > 0
    for t in q2.texts:
        assert set(json.loads(t)) == {"x"}
    f32.generate(rows, grammar=g, shared_prefix=PREFIX, max_new_tokens=32)
    assert q8.kv_peak_bytes < f32.kv_peak_bytes


def test_int8_dequant_drift_is_bounded():
    """Round-trip error of the per-page scale quantizer on real committed
    pages: |fp − dequant(int8)| ≤ scale/2 elementwise."""
    eng = _engine(kv_quant="int8", page_size=16)
    g = JsonGrammar([Field("x", "BOOLEAN")])
    eng.generate(["row alpha beta gamma"], grammar=g, shared_prefix=PREFIX,
                 max_new_tokens=8)
    flags = np.flatnonzero(eng._quant_flags > 0)
    assert flags.size > 0
    k = np.asarray(eng._pool["k"][:, :, flags], np.float32)
    kq = np.asarray(eng._pool["kq"][:, :, flags], np.float32)
    ks = np.asarray(eng._pool["kscale"][:, :, flags], np.float32)
    deq = kq * ks[..., None, None]
    # scale = amax/127 ⇒ |x/scale| ≤ 127: rounding is the only error source
    bound = np.broadcast_to(ks[..., None, None] * 0.5 + 1e-6, k.shape)
    np.testing.assert_array_less(np.abs(k - deq), bound)


# ------------------------ token-boundary prefix carve --------------------------
def test_executor_carve_token_boundary_multibyte():
    """Regression: prompts whose common prefix ends INSIDE a multi-byte
    character (δ vs ε share the UTF-8 lead byte 0xCE).  The carve must cut
    on a token (byte) boundary that still decodes — splitting mid-character
    would corrupt every suffix."""
    stem = "αβγ " * 20                      # > one 32-byte page of overlap
    prompts = [stem + "δ value one", stem + "ε value two",
               stem + "δ value three"]
    outs = {}
    for mode in ("dense", "exact", "radix"):
        if mode == "dense":
            eng = InferenceEngine(_cfg(), seed=0, max_len=512)
        else:
            eng = _engine(prefix_cache_mode=mode)
        ex = JaxExecutor(eng)
        ex.configure({"num_slots": 4, "temperature": 0.0, "max_tokens": 48})
        res = ex.complete_many(prompts, [("v", "INTEGER")], [1] * 3)
        outs[mode] = [r.text for r in res]
    assert outs["dense"] == outs["exact"] == outs["radix"]


# ------------------------------ SQL n_samples ---------------------------------
def test_sql_n_samples_self_consistency():
    db = IPDB()
    db.register_table("Items", Table.from_rows(
        [{"name": f"item {i}"} for i in range(4)]))
    eng = _engine(max_len=512)

    def factory(entry):
        ex = JaxExecutor(eng)
        ex.configure(dict(entry.options))
        return ex

    db.register_executor("t_jax", factory)
    # batch_size 1: each row is its own prompt, so the dispatch reaches the
    # batcher's multi-prompt path (forks + cross-prompt radix matching)
    db.sql("CREATE LLM MODEL anno PATH 'custom:t_jax' ON PROMPT "
           "OPTIONS { 'batch_size': 1, 'max_str': 6, 'temperature': 0.0, "
           "'num_slots': 4, 'max_tokens': 48, 'n_samples': 3 }")
    db.set_option("batch_size", 1)
    r = db.sql("SELECT name, LLM anno (PROMPT '" + PREFIX +
               "guess the {color VARCHAR} of {{name}}') AS color FROM Items")
    assert len(r.table.rows()) == 4
    assert r.stats.radix_hit_tokens > 0
    tree = eng._radix.resident_page_ids()
    assert eng._alloc.in_use == len(tree)
    db.close()


def test_deeper_radix_match_reuses_the_prefill_program():
    """Prefix tables are padded to a power of two (masked by prefix_len):
    a fill that matches one page deeper than the shared instruction runs
    the instruction's prefill program.  (That the padded pages are never
    read is checked against the float32 references with shared prefixes,
    bench/tests/test_bench_reference.py and test_bench_deepseek.py.)"""
    cfg = C.get_smoke_config("olmo-1b").replace(vocab_size=259)
    eng = InferenceEngine(cfg, seed=3, max_len=128, kv_layout="paged",
                          page_size=4)
    eng._ensure_pool(64)
    prefix = eng.alloc_pages(7)
    suffix = list(range(40, 50))
    table = np.full((1, eng.num_table_blocks), -1, np.int32)
    out = {}
    for npre in (6, 7):
        table[0, :npre] = prefix[:npre]
        table[0, npre:npre + 3] = eng.alloc_pages(3)
        out[npre] = eng.paged_prefill([suffix], table, prefix[:npre],
                                      npre * 4)[0]
    assert [k[4] for k in eng._prefill_cache if k[0] == "paged"] == [8]
    # the same suffix one page later: another answer, the same program
    assert not np.allclose(out[6], out[7])
