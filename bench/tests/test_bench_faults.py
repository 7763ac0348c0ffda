"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped and the rest of a run is driven at the
smoke sizes, with the cell's own limits.  The faults a served cell can
have: a decode step that returns its cache unchanged, half of a dispatch's
batch left unanswered, a token altered where it is produced.  (No cell
spans chips, so no exchange between chips can be left out.)"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness

CELLS = ["olmo-1b.reviews-batch", "olmo-1b.docs-shared",
         "olmo-1b.lookup-open-loop"]


def small_plan(cell):
    """The cell at a size a test run holds: fewer and shorter rows."""
    return shrink(harness.cell_plan(harness.load_benchmark(), cell))


def shrink(plan):
    """A cell's plan cut, in place, to fewer and shorter rows."""
    mix = plan["mix"]
    mix["warmup_rows"] = 4
    if mix["loop"] == "closed":
        mix.update(rows_per_query=8, max_queries=8)
        lengths = mix["table"]["length_tokens"]
        if lengths["dist"] == "uniform":
            lengths.update(min=192, max=320)
    else:
        mix["open"]["table_rows"] = 64
    return plan


def run(plan, seed=5, seconds=1.0, control=False):
    return harness.run_cell(plan, seed, seconds, False,
                            t_start=time.perf_counter(), smoke=True,
                            control=control)


def state_unchanged(monkeypatch):
    from repro.serving.engine import InferenceEngine
    dense, paged = InferenceEngine._decode_fn, InferenceEngine.paged_decode

    def decode_fn(self):
        step = dense(self)

        def unchanged(params, tokens, positions, cache):
            keep = jax.tree.map(jnp.copy, cache)
            return step(params, tokens, positions, cache)[0], keep
        return unchanged

    def paged_decode(self, *a, **kw):
        keep = {k: jnp.copy(v) for k, v in self._pool.items()}
        out = paged(self, *a, **kw)
        self._pool.update(keep)
        return out

    monkeypatch.setattr(InferenceEngine, "_decode_fn", decode_fn)
    monkeypatch.setattr(InferenceEngine, "paged_decode", paged_decode)


def half_batch(monkeypatch):
    from repro.serving.scheduler import ContinuousBatcher
    orig = ContinuousBatcher.run

    def half(self, requests, **kw):
        done = orig(self, requests, **kw)
        for r in done[::2]:
            r.text = None
        return done
    monkeypatch.setattr(ContinuousBatcher, "run", half)


def token_altered(monkeypatch):
    from repro.serving.engine import InferenceEngine
    orig = InferenceEngine._sample
    swap = {ord("t"): ord("f"), ord("f"): ord("t")}

    def altered(self, logits, gs, states, temperature):
        toks = orig(self, logits, gs, states, temperature)
        for b, (g, st) in enumerate(zip(gs, states)):
            if g is not None and st.sub == 0 and \
                    g.prog[st.pc][0] in ("str", "bool"):
                t = int(toks[b])
                toks[b] = swap.get(t, 65 if t != 65 else 66)
        return toks
    monkeypatch.setattr(InferenceEngine, "_sample", altered)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}


# a one-row lookup dispatches a batch of one: it has no half to leave out
CASES = [(c, f) for c in CELLS for f in sorted(FAULTS)
         if not (f == "half_batch" and "lookup" in c)]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    plan = small_plan(cell)
    FAULTS[fault](monkeypatch)
    out = run(plan)
    assert out["correct"] is False, out["checks"]
