"""The engine's step programs update the stacked KV cache, or page pool, in
place: each layer writes only its new tokens' K/V into the donated buffer.

The optimized program is read for any `broadcast`, `copy`, `select` or
`convert` whose result holds as many elements as the whole stacked cache or
pool: a zero-filled stack rebuilt by the layer scan, a copy of it back into
the donated buffer, a full-slice masked write, or a cast of the whole stack.
The steps are compiled at smoke widths with float32 compute: the CPU backend
upcasts a bfloat16 buffer around every in-place update, which the TPU does
not, and `tests/test_tpu_compile.py` holds the published widths to their
memory on the chip.  The latent pool of DeepSeek-V2-Lite is the control: it
has ridden in the scan's carry since it was added.

The paged prefill writes its tokens a whole page at a time; those writes are
held to one write per (kv-head, token) through the block table.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.models import model as MDL
from repro.serving.engine import InferenceEngine

B, MAX_LEN, PAGE, PAGES = 4, 64, 16, 16
NB = MAX_LEN // PAGE
WHOLE_BUFFER_OPS = ("broadcast", "copy", "select", "convert")
_INSTR = re.compile(r"%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def _engine(arch, layout):
    cfg = C.get_smoke_config(arch).replace(compute_dtype="float32")
    return InferenceEngine(cfg, seed=0, max_len=MAX_LEN, kv_layout=layout,
                           page_size=PAGE)


def _dense_decode(row_idx):
    eng = _engine("olmo-1b", "dense")
    cache = MDL.cache_specs(eng.cfg, B, MAX_LEN, include_row_idx=row_idx)
    return eng._decode_fn().lower(_shapes(eng.params), _i32(B, 1),
                                  _i32(B, 1), cache), cache["k"]


def _paged_decode(arch):
    eng = _engine(arch, "paged")
    pool = MDL.paged_cache_specs(eng.cfg, PAGES, PAGE, B)
    return eng._decode_fn_paged(NB).lower(
        _shapes(eng.params), _i32(B, 1), _i32(B, 1), pool, _i32(B, NB),
        None), pool[eng.pool_keys[0]]


def _paged_prefill():
    eng = _engine("olmo-1b", "paged")
    pool = MDL.paged_cache_specs(eng.cfg, PAGES, PAGE, 1)
    # a 32-token suffix behind two shared prefix pages
    return eng._paged_prefill_fn(1, 32, NB, 2).lower(
        _shapes(eng.params), _i32(1, 32), _i32(1, 32), pool, _i32(1, NB),
        _i32(2), _i32(), None), pool["k"]


STEPS = {
    "dense-decode-row-idx": lambda: _dense_decode(row_idx=True),
    "dense-decode-cursor": lambda: _dense_decode(row_idx=False),
    "paged-decode": lambda: _paged_decode("olmo-1b"),
    "paged-prefill": _paged_prefill,
    "latent-paged-decode": lambda: _paged_decode("deepseek-v2-lite"),
}


def whole_buffer_ops(hlo: str, size: int):
    """(opcode, shape) of every listed op whose result has `size` elements."""
    out = []
    for m in _INSTR.finditer(hlo):
        dims, op = m.group(1), m.group(2)
        if op in WHOLE_BUFFER_OPS and dims and \
                math.prod(map(int, dims.split(","))) == size:
            out.append((op, dims))
    return out


@pytest.mark.parametrize("step", list(STEPS))
def test_step_updates_the_stacked_cache_in_place(step):
    lowered, stack = STEPS[step]()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # the cache argument is donated: the program writes into it
    assert "input_output_alias" in hlo
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        math.prod(stack.shape) * stack.dtype.itemsize
    assert whole_buffer_ops(hlo, math.prod(stack.shape)) == []


# (prefix length, each row's token count, block-table entries set to -1):
# page-aligned and mid-page starts, ragged rows, a row that runs past its
# table, a missing page, a row of pads alone
PREFILL_WRITES = {
    "aligned": (8, (8, 5, 3), ()),
    "mid-page": (6, (7, 2, 8), ()),
    "dropped": (10, (8, 3, 6), ((1, 3),)),
    "pads-only-row": (5, (8, 0, 1), ()),
}


@pytest.mark.parametrize("case", list(PREFILL_WRITES))
def test_prefill_page_writes_match_token_writes(case):
    """The paged prefill's whole-page writes leave the pool exactly as one
    write per (kv-head, token) through `_page_slots` does."""
    plen, lens, missing = PREFILL_WRITES[case]
    NL, KV, P, ps, D, Dp, S, nb = 2, 2, 16, 4, 6, 8, 8, 4
    B = len(lens)
    pos = jnp.full((B, S), -1, jnp.int32)
    for b, n in enumerate(lens):
        pos = pos.at[b, S - n:].set(plen + jnp.arange(n, dtype=jnp.int32))
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)[:, ::-1]
    for b, j in missing:
        bt = bt.at[b, j].set(-1)
    kx, kp = jax.random.split(jax.random.PRNGKey(len(case)))
    x = jax.random.normal(kx, (B, S, KV, D))
    pool = jax.random.normal(kp, (NL, KV, P, ps, Dp))
    for layer in range(NL):
        want = jax.jit(lambda pool: MDL._write_pages(
            pool, layer, *MDL._page_slots(bt, pos, P, ps), x))(pool)
        got = jax.jit(lambda pool: MDL._write_prefill_pages(
            pool, layer, bt, pos, x))(pool)
        assert bool((want != pool).any())
        assert jnp.array_equal(got, want)
