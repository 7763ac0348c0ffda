"""Compile the served path's kernels and steps for a described TPU v5e chip
at OLMo-1B's published widths.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached.  It refuses what interpret mode lets
through — block shapes off the (8, 128) tiling, more VMEM than a kernel may
use, a program larger than the chip's HBM.  The topology is described
inside a fixture (never at import) so that every test worker collects the
same tests and only the worker running this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.kernels import ops
from repro.models import model as MDL
from repro.serving.engine import InferenceEngine

CFG = C.get_config("olmo-1b")
HBM_BYTES = 16 * 10 ** 9              # one TPU v5e chip
SLOTS, MAX_LEN, PAGE = 8, 2048, 64    # chip_smoke.py's engine geometry
H, KV, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_cases():
    """name -> (fn, [(shape, dtype)...]) at OLMo-1B widths."""
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    P, NB = SLOTS * MAX_LEN // PAGE, MAX_LEN // PAGE
    pool = (KV, P, PAGE, D)
    V = CFG.padded_vocab

    def paged_quant(q, k, v, bt, qp, kq, vq, ks, vs, fl):
        return ops.decode_attention_paged(
            q, k, v, bt, qp, interpret=False,
            quant=dict(kq=kq, vq=vq, kscale=ks, vscale=vs, flags=fl))

    return {
        "decode_dense": (
            functools.partial(ops.decode_attention, interpret=False),
            [((SLOTS, H, D), bf), ((SLOTS, MAX_LEN, KV, D), bf),
             ((SLOTS, MAX_LEN, KV, D), bf), ((SLOTS, MAX_LEN), i32),
             ((SLOTS,), i32)]),
        "decode_paged": (
            functools.partial(ops.decode_attention_paged, interpret=False),
            [((SLOTS, H, D), bf), (pool, bf), (pool, bf),
             ((SLOTS, NB), i32), ((SLOTS,), i32)]),
        "decode_paged_quant": (
            paged_quant,
            [((SLOTS, H, D), bf), (pool, bf), (pool, bf),
             ((SLOTS, NB), i32), ((SLOTS,), i32), (pool, jnp.int8),
             (pool, jnp.int8), ((KV, P), f32), ((KV, P), f32),
             ((P,), jnp.int8)]),
        # the engine's sampler call: padded vocab, block_v=256
        "sampler": (
            functools.partial(ops.constrained_sample, interpret=False,
                              block_v=256),
            [((SLOTS, V), f32), ((SLOTS, V), jnp.int8), ((SLOTS, V), f32)]),
        "flash": (
            functools.partial(ops.flash_attention, interpret=False),
            [((2, 1024, H, D), bf), ((2, 1024, KV, D), bf),
             ((2, 1024, KV, D), bf), ((2, 1024), i32), ((2, 1024), i32)]),
        "gmm": (
            functools.partial(ops.gmm, interpret=False),
            [((256, CFG.d_model), bf), ((8, CFG.d_model, CFG.d_ff), bf),
             ((8,), i32)]),
    }


@pytest.mark.parametrize("name", list(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Mosaic kernel itself is in the program, not an interpreted twin
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_full_width_decode_step_fits_one_chip(one_chip, layout):
    # params as shapes: the engine allocates nothing until it serves
    eng = InferenceEngine(CFG, params=_on(one_chip, MDL.param_specs(CFG)),
                          max_len=MAX_LEN, kv_layout=layout, page_size=PAGE)
    tok = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=one_chip)
    if layout == "dense":
        cache = _on(one_chip, MDL.cache_specs(CFG, SLOTS, MAX_LEN,
                                              include_row_idx=True))
        lowered = eng._decode_fn().lower(eng.params, tok, tok, cache)
    else:
        P, NB = SLOTS * MAX_LEN // PAGE, MAX_LEN // PAGE
        cache = _on(one_chip, MDL.paged_cache_specs(CFG, P, PAGE, SLOTS))
        bt = jax.ShapeDtypeStruct((SLOTS, NB), jnp.int32, sharding=one_chip)
        lowered = eng._decode_fn_paged(NB).lower(eng.params, tok, tok, cache,
                                                 bt, None)
    mem = lowered.compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # fp32 weights alone are ~4.7 GB; the whole step must fit the chip
    assert 4e9 < used < HBM_BYTES, used
