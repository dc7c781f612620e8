"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.unified_pd import build_slot_schedule, unified_pd

TOL = {jnp.float32: dict(atol=3e-5, rtol=3e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def _rand(rng, shape, dtype):
    return jax.random.normal(rng, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash_prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,S,D,bq,bk,window", [
    (2, 4, 2, 128, 32, 64, 64, None),
    (1, 8, 2, 257, 64, 64, 128, None),     # ragged S (padding path)
    (2, 4, 4, 256, 32, 64, 64, 96),        # sliding window
    (1, 2, 1, 64, 16, 32, 32, None),       # MQA
    (1, 4, 1, 96, 32, 32, 32, 32),         # window == block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill(rng, B, Hq, Hkv, S, D, bq, bk, window, dtype):
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (B, Hq, S, D), dtype)
    k = _rand(ks[1], (B, Hkv, S, D), dtype)
    v = _rand(ks[2], (B, Hkv, S, D), dtype)
    out = flash_prefill(q, k, v, window=window, block_q=bq, block_k=bk,
                        interpret=True)
    want = ref.causal_attention(q, k, v, window=window)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,D,page,max_pages,N", [
    (2, 4, 2, 32, 8, 4, 16),
    (3, 8, 4, 64, 16, 6, 32),
    (1, 4, 1, 16, 8, 3, 8),
    (4, 2, 2, 32, 4, 5, 24),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(rng, B, Hq, Hkv, D, page, max_pages, N, dtype):
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (B, Hq, D), dtype)
    kp = _rand(ks[1], (Hkv, N, page, D), dtype)
    vp = _rand(ks[2], (Hkv, N, page, D), dtype)
    rs = np.random.RandomState(0)
    tabs = jnp.asarray(np.stack(
        [rs.permutation(N)[:max_pages] for _ in range(B)]).astype(np.int32))
    lens = jnp.asarray(
        rs.randint(1, max_pages * page + 1, size=B).astype(np.int32))
    out = paged_attention(q, kp, vp, tabs, lens, interpret=True)
    want = ref.paged_attention(q, kp, vp, tabs, lens)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


def test_paged_attention_len_one(rng):
    """Boundary: a sequence with exactly one valid token."""
    B, Hq, Hkv, D, page, mp, N = 2, 4, 2, 32, 8, 3, 8
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (B, Hq, D), jnp.float32)
    kp = _rand(ks[1], (Hkv, N, page, D), jnp.float32)
    vp = _rand(ks[2], (Hkv, N, page, D), jnp.float32)
    tabs = jnp.tile(jnp.arange(mp, dtype=jnp.int32), (B, 1))
    lens = jnp.array([1, page * mp], jnp.int32)
    out = paged_attention(q, kp, vp, tabs, lens, interpret=True)
    want = ref.paged_attention(q, kp, vp, tabs, lens)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,L,din,ds,chunk,tile", [
    (2, 64, 32, 8, 16, 16),
    (1, 128, 64, 16, 32, 32),
    (2, 96, 48, 4, 24, 24),
    (1, 60, 40, 8, 16, 16),     # chunk/tile fallback (60 % 16 != 0)
])
def test_ssm_scan(rng, B, L, din, ds, chunk, tile):
    ks = jax.random.split(rng, 5)
    xs = jax.random.normal(ks[0], (B, L, din), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, din)))
    A = -jnp.exp(jax.random.normal(ks[2], (din, ds)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, ds))
    Cm = jax.random.normal(ks[4], (B, L, ds))
    y, h = ssm_scan(xs, dt, A, Bm, Cm, chunk=chunk, tile_d=tile,
                    interpret=True)
    y_ref, h_ref = ref.ssm_scan(xs, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, y_ref, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, h_ref, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# unified_pd — the paper's concurrent P/D step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("f_decode", [1.0, 0.5, 0.25, 0.1])
def test_slot_schedule(f_decode):
    kinds = build_slot_schedule(24, 6, f_decode)
    assert kinds.sum() == 6 and len(kinds) == 30
    dpos = np.where(kinds == 1)[0]
    # decode tiles finish within ~n_d / f_decode slots (+rounding)
    assert dpos[-1] <= int(6 / f_decode) + 6


@pytest.mark.parametrize("Bp,Bd,Hq,Hkv,Sp,D,page,mp,N,f,win", [
    (1, 2, 4, 2, 128, 32, 8, 4, 16, 0.5, None),
    (2, 3, 4, 4, 64, 16, 8, 3, 12, 0.25, None),
    (1, 2, 8, 2, 96, 32, 16, 2, 8, 1.0, 48),
    (2, 1, 4, 2, 64, 32, 8, 2, 8, 0.1, None),
])
def test_unified_pd(rng, Bp, Bd, Hq, Hkv, Sp, D, page, mp, N, f, win):
    ks = jax.random.split(rng, 6)
    q_p = _rand(ks[0], (Bp, Hq, Sp, D), jnp.float32)
    k_p = _rand(ks[1], (Bp, Hkv, Sp, D), jnp.float32)
    v_p = _rand(ks[2], (Bp, Hkv, Sp, D), jnp.float32)
    q_d = _rand(ks[3], (Bd, Hq, D), jnp.float32)
    kpg = _rand(ks[4], (Hkv, N, page, D), jnp.float32)
    vpg = _rand(ks[5], (Hkv, N, page, D), jnp.float32)
    rs = np.random.RandomState(1)
    tabs = jnp.asarray(np.stack(
        [rs.permutation(N)[:mp] for _ in range(Bd)]).astype(np.int32))
    lens = jnp.asarray(
        rs.randint(1, mp * page + 1, size=Bd).astype(np.int32))
    o_p, o_d = unified_pd(q_p, k_p, v_p, q_d, kpg, vpg, tabs, lens,
                          f_decode=f, window=win, block_q=32, block_k=32,
                          interpret=True)
    rp, rd = ref.unified_pd(q_p, k_p, v_p, q_d, kpg, vpg, tabs, lens,
                            window=win)
    np.testing.assert_allclose(o_p, rp, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(o_d, rd, atol=3e-5, rtol=3e-5)


def test_unified_pd_matches_single_kernels(rng):
    """The fused step must agree with the standalone kernels exactly
    (same accumulation order per tile)."""
    Bp, Bd, Hq, Hkv, Sp, D, page, mp, N = 1, 2, 4, 2, 64, 32, 8, 3, 12
    ks = jax.random.split(rng, 6)
    q_p = _rand(ks[0], (Bp, Hq, Sp, D), jnp.float32)
    k_p = _rand(ks[1], (Bp, Hkv, Sp, D), jnp.float32)
    v_p = _rand(ks[2], (Bp, Hkv, Sp, D), jnp.float32)
    q_d = _rand(ks[3], (Bd, Hq, D), jnp.float32)
    kpg = _rand(ks[4], (Hkv, N, page, D), jnp.float32)
    vpg = _rand(ks[5], (Hkv, N, page, D), jnp.float32)
    tabs = jnp.tile(jnp.arange(mp, dtype=jnp.int32), (Bd, 1))
    lens = jnp.array([5, page * mp], jnp.int32)
    o_p, o_d = unified_pd(q_p, k_p, v_p, q_d, kpg, vpg, tabs, lens,
                          f_decode=0.5, block_q=32, block_k=32,
                          interpret=True)
    o_p2 = flash_prefill(q_p, k_p, v_p, block_q=32, block_k=32,
                         interpret=True)
    o_d2 = paged_attention(q_d, kpg, vpg, tabs, lens, interpret=True)
    np.testing.assert_allclose(o_p, o_p2, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(o_d, o_d2, atol=1e-6, rtol=1e-6)
