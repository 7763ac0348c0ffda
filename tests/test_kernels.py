"""Per-kernel allclose sweeps (interpret=True) against the ref.py oracles."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _split(n):
    return jax.random.split(KEY, n)


# ------------------------------ flash attention -------------------------------
@pytest.mark.parametrize("B,Sq,H,KV,D", [
    (1, 65, 4, 2, 16), (2, 128, 4, 4, 32), (1, 200, 8, 1, 64),
    (2, 96, 4, 2, 80),          # hubert head_dim (pads to 128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,prefix", [
    (True, 0, 0), (True, 17, 0), (False, 0, 0), (True, 0, 11),
])
def test_flash_attention(B, Sq, H, KV, D, dtype, causal, window, prefix):
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sq, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sq, KV, D), dtype)
    pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))
    out = ops.flash_attention(q, k, v, pos, pos, causal=causal, window=window,
                              prefix_len=prefix, block_q=64, block_kv=64,
                              interpret=True)
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, D).transpose(0, 2, 3, 1, 4).reshape(B * KV, G, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Sq, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Sq, D)
    pr = jnp.repeat(pos, KV, axis=0)
    r = ref.flash_attention_ref(qr, kr, vr, pr, pr, causal=causal,
                                window=window, prefix_len=prefix)
    r = r.reshape(B, KV, G, Sq, D).transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# ------------------------------ decode attention ------------------------------
@pytest.mark.parametrize("B,H,KV,D,L,fill", [
    (2, 4, 2, 32, 96, 50), (1, 8, 1, 64, 128, 128), (3, 4, 4, 80, 64, 10),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, KV, D, L, fill, dtype):
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, L, KV, D), dtype)
    vc = jax.random.normal(ks[2], (B, L, KV, D), dtype)
    spos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
    spos = jnp.where(spos < fill, spos, -1)
    qpos = jnp.full((B,), fill - 1, jnp.int32)
    out = ops.decode_attention(q, kc, vc, spos, qpos, block_l=32,
                               interpret=True)
    G = H // KV
    qr = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kr = kc.transpose(0, 2, 1, 3).reshape(B * KV, L, D)
    vr = vc.transpose(0, 2, 1, 3).reshape(B * KV, L, D)
    r = ref.decode_attention_ref(
        qr, kr, vr, jnp.repeat(spos, KV, axis=0),
        jnp.repeat(qpos[:, None], KV, axis=0).reshape(B * KV, 1))
    r = r.reshape(B, KV, G, D).reshape(B, H, D)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# --------------------------- paged decode attention ---------------------------
def _folded_pools(key, KV, P, ps, D, dtype):
    """Random pool in the pre-folded TPU-native layout (KV, P, ps, Dp) —
    data in the first D lanes, zero lane padding — plus the unpadded
    (KV·P, ps, D) view the oracle consumes."""
    from repro.models.model import padded_head_dim
    Dp = padded_head_dim(D)
    raw = jax.random.normal(key, (KV, P, ps, D), dtype)
    pool = jnp.pad(raw, ((0, 0), (0, 0), (0, 0), (0, Dp - D)))
    return pool, raw.reshape(KV * P, ps, D)


def _ragged_tables(B, KV, P, ps, NB, seed):
    rng = np.random.default_rng(seed)
    fills = [int(rng.integers(1, NB * ps + 1)) for _ in range(B)]
    bt = np.full((B, NB), -1, np.int32)
    perm = iter(rng.permutation(P))
    for b, f in enumerate(fills):
        for j in range((f + ps - 1) // ps):
            bt[b, j] = next(perm)
    bt = jnp.asarray(bt)
    qpos = jnp.asarray([f - 1 for f in fills], jnp.int32)
    nact = jnp.asarray([(f - 1) // ps + 1 for f in fills], jnp.int32)
    btf = (jnp.clip(bt, 0, P - 1)[:, None, :]
           + jnp.arange(KV)[None, :, None] * P).reshape(B * KV, NB)
    return bt, qpos, jnp.repeat(nact, KV), btf, \
        jnp.repeat(qpos[:, None], KV, axis=0).reshape(B * KV, 1)


@pytest.mark.parametrize("B,H,KV,D,ps,NB,P", [
    (2, 4, 2, 32, 16, 4, 12), (1, 8, 1, 64, 32, 2, 6),
    (3, 4, 4, 80, 8, 8, 32),            # pads D to 128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_paged(B, H, KV, D, ps, NB, P, dtype):
    """Block-table Pallas kernel on the pre-folded (KV, P, ps, Dp) pool vs
    the gather-based jnp oracle, ragged fills (some rows one block, some
    full)."""
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp, kf = _folded_pools(ks[1], KV, P, ps, D, dtype)
    vp, vf = _folded_pools(ks[2], KV, P, ps, D, dtype)
    bt, qpos, nactf, btf, qposf = _ragged_tables(B, KV, P, ps, NB, B * 7 + NB)

    out = ops.decode_attention_paged(q, kp, vp, bt, qpos, head_dim=D,
                                     interpret=True)

    G = H // KV
    qr = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    r = ref.decode_attention_paged_ref(qr, kf, vf, btf, nactf, qposf)
    r = r.reshape(B, KV, G, D).reshape(B, H, D)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,KV,D,ps,NB,P", [
    (2, 4, 2, 32, 16, 4, 12), (3, 4, 4, 80, 8, 8, 32),
])
def test_decode_attention_paged_quant(B, H, KV, D, ps, NB, P):
    """Dequantizing kernel twin vs the quant-aware oracle: half the pages
    frozen into int8 shadows with per-page scales, half live in fp."""
    from repro.models.model import padded_head_dim
    Dp = padded_head_dim(D)
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp, kf = _folded_pools(ks[1], KV, P, ps, D, jnp.float32)
    vp, vf = _folded_pools(ks[2], KV, P, ps, D, jnp.float32)
    bt, qpos, nactf, btf, qposf = _ragged_tables(B, KV, P, ps, NB, 11)

    # freeze the even pages: per-(kv-head, page) scale over the page block
    flags = jnp.asarray([1 - (p % 2) for p in range(P)], jnp.int32)

    def quantize(pool):
        amax = jnp.max(jnp.abs(pool), axis=(2, 3))          # (KV, P)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        qv = jnp.clip(jnp.round(pool / scale[..., None, None]),
                      -127, 127).astype(jnp.int8)
        return qv, scale

    kq, kscale = quantize(kp)
    vq, vscale = quantize(vp)
    quant = {"kq": kq, "vq": vq, "kscale": kscale, "vscale": vscale,
             "flags": flags}
    out = ops.decode_attention_paged(q, kp, vp, bt, qpos, head_dim=D,
                                     quant=quant, interpret=True)

    G = H // KV
    qr = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    flf = jnp.tile(flags[None, :], (KV, 1)).reshape(KV * P, 1)
    r = ref.decode_attention_paged_quant_ref(
        qr, kf, vf, kq.reshape(KV * P, ps, Dp)[..., :D],
        vq.reshape(KV * P, ps, Dp)[..., :D],
        kscale.reshape(KV * P, 1), vscale.reshape(KV * P, 1),
        flf, btf, nactf, qposf)
    r = r.reshape(B, KV, G, D).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               atol=3e-5, rtol=3e-5)
    # and the dequantized path stays within int8 drift of the fp oracle
    rf = ref.decode_attention_paged_ref(qr, kf, vf, btf, nactf, qposf)
    rf = rf.reshape(B, KV, G, D).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rf),
                               atol=0.08, rtol=0.08)


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_attention_paged_reads_a_stacked_pool(quantized):
    """Pools stacked over layers and read at `layer` give what the layer's
    own pool gives, in the kernel (a free view of the whole stack, offset
    by the layer's pages) and in its jnp twin (one gather from the
    stack)."""
    from repro.models import layers as LY
    B, H, KV, D, ps, NB, P, NL = 2, 4, 2, 32, 16, 4, 12, 3
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jnp.stack([_folded_pools(k, KV, P, ps, D, jnp.float32)[0]
                    for k in jax.random.split(ks[1], NL)])
    vp = jnp.stack([_folded_pools(k, KV, P, ps, D, jnp.float32)[0]
                    for k in jax.random.split(ks[2], NL)])
    bt, qpos, _, _, _ = _ragged_tables(B, KV, P, ps, NB, 11)
    quant = None
    if quantized:
        amax = jnp.max(jnp.abs(kp), axis=(3, 4))            # (NL, KV, P)
        kscale = jnp.maximum(amax, 1e-8) / 127.0
        vscale = jnp.maximum(jnp.max(jnp.abs(vp), axis=(3, 4)), 1e-8) / 127.0
        quant = {"kq": jnp.round(kp / kscale[..., None, None]).astype(jnp.int8),
                 "vq": jnp.round(vp / vscale[..., None, None]).astype(jnp.int8),
                 "kscale": kscale, "vscale": vscale,
                 "flags": jnp.asarray([p % 2 for p in range(P)], jnp.int32)}
    for layer in range(NL):
        one = None if quant is None else \
            {k: (x if k == "flags" else x[layer]) for k, x in quant.items()}
        for fn in (functools.partial(ops.decode_attention_paged,
                                     interpret=True),
                   LY.decode_attention_paged):
            want = fn(q, kp[layer], vp[layer], bt, qpos, head_dim=D,
                      quant=one)
            got = fn(q, kp, vp, bt, qpos, head_dim=D, quant=quant,
                     layer=jnp.int32(layer))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_attention_paged_shared_prefix_pages():
    """Rows sharing the SAME prefix pages read them in place and attend
    identically to a dense replication of that prefix."""
    from repro.models import layers as L
    B, H, KV, D, ps = 4, 4, 2, 32, 8
    P, NB = 8, 3
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp, _ = _folded_pools(ks[1], KV, P, ps, D, jnp.float32)
    vp, _ = _folded_pools(ks[2], KV, P, ps, D, jnp.float32)
    # every row: shared pages [1, 2] + its own page (3 + b); fill = 20
    bt = jnp.asarray([[1, 2, 3 + b] for b in range(B)], jnp.int32)
    qpos = jnp.full((B,), 19, jnp.int32)
    out = ops.decode_attention_paged(q, kp, vp, bt, qpos, head_dim=D,
                                     interpret=True)

    kd = kp[:, bt, :, :D].transpose(1, 2, 3, 0, 4).reshape(B, NB * ps, KV, D)
    vd = vp[:, bt, :, :D].transpose(1, 2, 3, 0, 4).reshape(B, NB * ps, KV, D)
    spos = jnp.broadcast_to(jnp.arange(NB * ps, dtype=jnp.int32)[None],
                            (B, NB * ps))
    r = L.decode_attention(q, kd, vd, spos, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               atol=3e-5, rtol=3e-5)


# --------------------------------- MoE gmm ------------------------------------
@pytest.mark.parametrize("T,M,N,E,seed", [
    (64, 32, 48, 4, 0), (130, 64, 64, 8, 1), (33, 96, 16, 3, 2),
    (16, 32, 32, 5, 3),
])
def test_gmm(T, M, N, E, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    gs = np.zeros(E, np.int64)
    r = np.random.default_rng(seed)
    for _ in range(T):
        gs[r.integers(0, E)] += 1
    x = jax.random.normal(k2, (T, M))
    w = jax.random.normal(k3, (E, M, N)) * 0.1
    out = ops.gmm(x, w, jnp.asarray(gs), block_m=16, block_n=16, block_k=32,
                  interpret=True)
    rr = ref.gmm_ref(x, w, jnp.asarray(gs))
    np.testing.assert_allclose(np.asarray(out), np.asarray(rr),
                               atol=3e-5, rtol=3e-5)


# ------------------------------ selective scan --------------------------------
@pytest.mark.parametrize("Bz,S,Di,N", [(1, 48, 32, 8), (2, 100, 24, 16),
                                       (2, 33, 128, 4)])
def test_selective_scan(Bz, S, Di, N):
    ks = _split(5)
    u = jax.random.normal(ks[0], (Bz, S, Di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, S, Di))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.3)
    B = jax.random.normal(ks[3], (Bz, S, N)) * 0.5
    C = jax.random.normal(ks[4], (Bz, S, N)) * 0.5
    D = jnp.ones((Di,))
    y, h = ops.selective_scan(u, dt, A, B, C, D, chunk=16, block_d=16,
                              interpret=True)
    yr, hr = ref.selective_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4,
                               rtol=1e-4)


# --------------------------- constrained sampling -----------------------------
@pytest.mark.parametrize("B,V,temp", [(2, 512, 1.0), (4, 1000, 0.5),
                                      (1, 300, 2.0)])
def test_constrained_sample(B, V, temp):
    ks = _split(3)
    logits = jax.random.normal(ks[0], (B, V))
    mask = jax.random.uniform(ks[1], (B, V)) > 0.6
    mask = mask.at[:, 7].set(True)          # never fully masked
    noise = jax.random.gumbel(ks[2], (B, V))
    out = ops.constrained_sample(logits, mask, noise, temperature=temp,
                                 block_v=128, interpret=True)
    r = ref.constrained_sample_ref(logits, mask, noise, temperature=temp)
    assert np.array_equal(np.asarray(out), np.asarray(r))
    # sampled tokens always satisfy the mask
    assert bool(np.all(np.asarray(mask)[np.arange(B), np.asarray(out)]))


def test_constrained_sample_greedy():
    logits = jnp.asarray([[1.0, 5.0, 3.0, -2.0]])
    mask = jnp.asarray([[1, 0, 1, 1]], jnp.int8)
    out = ops.constrained_sample(logits, mask, None, block_v=4, interpret=True)
    assert int(out[0]) == 2                  # best *allowed* token


# -------------------------- jnp flash (model layer) ----------------------------
def test_model_flash_vs_reference_grad():
    from repro.models import layers as L
    B, S, H, KV, D = 2, 50, 4, 2, 16
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    f = lambda *a: L.flash_attention(*a, pos, pos, True, 13, 0, 32, 32).sum()
    r = lambda *a: L.reference_attention(*a, pos, pos, True, 13, 0).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


# ------------------------- banded SWA flash (§Perf opt A) ----------------------
@pytest.mark.parametrize("win,bq,bkv", [(16, 32, 32), (33, 32, 64),
                                        (100, 64, 32)])
def test_banded_flash_matches_reference(win, bq, bkv):
    from repro.models import layers as L
    B, S, H, KV, D = 2, 300, 4, 2, 16
    ks = _split(3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    a = L.flash_attention(q, k, v, pos, pos, True, win, 0, bq, bkv,
                          False, True)
    b = L.reference_attention(q, k, v, pos, pos, True, win, 0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)
