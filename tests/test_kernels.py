"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.kernels import ops, ref
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import (BLOCK_BYTES, block_tokens,
                                           kv_append, kv_page,
                                           paged_attention)
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
    (2, 4, 4, 64, 16, 4, 16),       # MHA (G=1), D=64
    (3, 3, 3, 64, 8, 5, 24),        # MHA, odd head count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(rng, B, Hq, Hkv, D, page, max_pages, N, dtype):
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (B, Hq, D), dtype)
    kp = _rand(ks[1], (Hkv, N, D, page), dtype)
    vp = _rand(ks[2], (Hkv, N, D, page), dtype)
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
    kp = _rand(ks[1], (Hkv, N, D, page), jnp.float32)
    vp = _rand(ks[2], (Hkv, N, D, page), jnp.float32)
    tabs = jnp.tile(jnp.arange(mp, dtype=jnp.int32), (B, 1))
    lens = jnp.array([1, page * mp], jnp.int32)
    out = paged_attention(q, kp, vp, tabs, lens, interpret=True)
    want = ref.paged_attention(q, kp, vp, tabs, lens)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("L,layer,Hq,Hkv,D,page,row,lens,window,dtype", [
    pytest.param(3, 2, 4, 2, 32, 8, 32, (1, 11, 24), None, F32,
                 id="3-2-4-2-32-8-4"),         # rows of 4 pages, GQA
    pytest.param(2, 1, 4, 4, 64, 16, 32, (1, 19, 48), None, F32,
                 id="2-1-4-4-64-16-2"),        # MHA, D=64
    pytest.param(4, 0, 2, 1, 16, 8, 8, (1, 11, 24), None, F32,
                 id="4-0-2-1-16-8-1"),         # one page per row: a pool
    # dense slot caches through ops.paged_attention_dense: lengths of 1,
    # ending mid-block and on a block's end; blocks of 128-512 tokens
    pytest.param(2, 1, 4, 4, 64, None, 1024, (1, 300, 512, 1024), None,
                 BF16, id="dense-mha-d64-bf16-T512"),
    pytest.param(2, 0, 4, 4, 64, None, 1024, (1, 513, 1000, 1024), None,
                 F32, id="dense-mha-d64-f32-T512"),
    pytest.param(2, 1, 36, 36, 64, None, 512, (1, 130, 256, 512), None,
                 BF16, id="dense-mha36-d64-bf16-T128"),
    pytest.param(2, 1, 32, 4, 128, None, 1024, (1, 700, 1024, 3000), 1024,
                 BF16, id="dense-g8-bf16-T512-ring"),
    pytest.param(2, 1, 32, 4, 128, None, 1024, (1, 255, 256, 900), None,
                 F32, id="dense-g8-f32-T256"),
    pytest.param(2, 1, 24, 2, 128, None, 2048, (1, 1000, 1536, 2048), None,
                 BF16, id="dense-g12-bf16-T512"),
    pytest.param(2, 0, 24, 2, 128, None, 1024, (1, 1024, 600, 5000), 1024,
                 F32, id="dense-g12-f32-T512-ring"),
])
def test_paged_attention_stacked_layer(rng, L, layer, Hq, Hkv, D, page, row,
                                       lens, window, dtype):
    """A stacked cache (L, Hkv, R, D, row) read at ``layer`` equals that
    layer's pages, unrolled to a (Hkv, R*row/page, D, page) pool.  With
    no ``page`` it is a dense slot cache, slot b row b, read through
    ``ops.paged_attention_dense`` in blocks of ``dense_block_tokens``
    (a window's ring at ``min(len, window)``): each slot's row is one
    page of the reference's pool."""
    B = len(lens)
    R = 5 if page else B
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (B, Hq, D), dtype)
    kc = _rand(ks[1], (L, Hkv, R, D, row), dtype)
    vc = _rand(ks[2], (L, Hkv, R, D, row), dtype)
    seq = jnp.array(lens, jnp.int32)
    if page:
        npr = row // page
        rs = np.random.RandomState(2)
        tabs = jnp.asarray(np.stack(
            [rs.permutation(R * npr)[:3] for _ in range(B)]).astype(np.int32))
        out = paged_attention(q, kc, vc, tabs, seq, jnp.int32(layer),
                              page=page, interpret=True)

        def pool(c):
            return c[layer].reshape(Hkv, R, D, npr, page).transpose(
                0, 1, 3, 2, 4).reshape(Hkv, R * npr, D, page)
    else:
        assert row // ops.dense_block_tokens(kc) > 1
        out = ops.paged_attention_dense(q, kc, vc, jnp.int32(layer), seq,
                                        window=window, interpret=True)
        tabs = jnp.arange(B, dtype=jnp.int32)[:, None]
        seq = jnp.minimum(seq, window or row)

        def pool(c):
            return c[layer]

    want = ref.paged_attention(q, pool(kc), pool(vc), tabs, seq)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


# the benchmark's dense decode caches: (config, tokens a slot's row
# holds, the block the kernel reads)
SERVED_ROWS = [("starcoder2-3b", 2048, 512), ("minicpm-2b", 2048, 128),
               ("mellum2-12b-a2.5b", 8192, 512),
               ("mellum2-12b-a2.5b", 1024, 512)]     # its window rings


@pytest.mark.parametrize("arch,row,block", SERVED_ROWS)
def test_block_rule_at_served_shapes(arch, row, block):
    """A grid step reads a multiple of 128 tokens that divides the row,
    at most 1 MiB of K and V past 128; the clamped index map fetches
    each slot's live blocks, never one past its last, once each."""
    cfg = get_config(arch)
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    T = block_tokens(row, Hkv, D, 2)
    assert T == block and T % 128 == 0 and row % T == 0
    assert T == 128 or T * 2 * Hkv * D * 2 <= BLOCK_BYTES
    nb = row // T
    n = np.arange(1, row + 1)                     # one slot per length
    tab = (np.arange(len(n))[:, None] * nb + np.arange(nb)).astype(np.int32)
    b, j = np.arange(len(n))[:, None], np.arange(nb)[None, :]
    idx = kv_page(b, j, tab, n, np.array([1]), page=T, per_row=nb)
    lay, _, r, _, col = (np.broadcast_to(np.asarray(x), (len(n), nb))
                         for x in idx)
    last = (n - 1) // T
    assert (lay == 1).all() and (r == b).all()
    assert (col == np.minimum(j, last[:, None])).all()
    assert ((col != np.roll(col, 1, axis=1))[:, 1:].sum(1) + 1
            == -(-n // T)).all()


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
    # the fused kernel keeps (Hkv, N, page, D) pages; paged_attention
    # reads (Hkv, N, D, page)
    o_d2 = paged_attention(q_d, kpg.swapaxes(2, 3), vpg.swapaxes(2, 3),
                           tabs, lens, interpret=True)
    np.testing.assert_allclose(o_p, o_p2, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(o_d, o_d2, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# kv_append — decode's in-place column write
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L,layer,Hkv,B,D,S,page", [
    (2, 1, 2, 3, 32, 64, 16),       # rows of 4 pages, GQA widths
    (3, 0, 4, 2, 64, 32, 32),       # MHA, D=64, one page per row
    (4, 3, 1, 4, 16, 48, 16),
])
def test_kv_append(rng, L, layer, Hkv, B, D, S, page):
    """Equals a scatter of each slot's column into the stacked cache;
    every other element keeps its value.  Slots past the row clamp to
    its last token, as a dynamic_update_slice would."""
    ks = jax.random.split(rng, 4)
    kc = _rand(ks[0], (L, Hkv, B, D, S), jnp.bfloat16)
    vc = _rand(ks[1], (L, Hkv, B, D, S), jnp.bfloat16)
    kn = _rand(ks[2], (B, Hkv, D), jnp.float32)
    vn = _rand(ks[3], (B, Hkv, D), jnp.float32)
    slot = jnp.asarray(np.random.RandomState(3).randint(0, S, size=B),
                       jnp.int32).at[-1].set(S + 5)
    ko, vo = kv_append(kc, vc, kn, vn, slot, jnp.int32(layer), page=page,
                       interpret=True)
    at = jnp.minimum(slot, S - 1)
    b = jnp.arange(B)
    want_k = kc.at[layer, :, b, :, at].set(kn.astype(kc.dtype))
    want_v = vc.at[layer, :, b, :, at].set(vn.astype(vc.dtype))
    np.testing.assert_array_equal(ko, want_k)
    np.testing.assert_array_equal(vo, want_v)


# ---------------------------------------------------------------------------
# moe_gmm — grouped SwiGLU over the held experts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes,d,f", [
    ((5, 0, 1, 20, 6), 64, 32),        # an empty and a one-row expert
    ((0, 0, 0, 1, 31), 32, 16),        # one pair held, the rest elsewhere
    ((8, 8, 8, 8, 0), 128, 64),        # every row held
    ((0, 0, 0, 0, 16), 32, 16),        # nothing held: all rows zero
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(rng, sizes, d, f, dtype):
    """Rows of held expert e through expert e's SwiGLU, the last group's
    rows (experts on other chips) zero, held experts with no row or one
    row included."""
    from repro.kernels.moe_gmm import moe_gmm
    Eh = len(sizes) - 1
    ks = jax.random.split(rng, 4)
    m = sum(sizes)
    xs = _rand(ks[0], (m, d), dtype)
    wg = _rand(ks[1], (Eh, d, f), dtype) / d ** 0.5
    wi = _rand(ks[2], (Eh, d, f), dtype) / d ** 0.5
    wo = _rand(ks[3], (Eh, f, d), dtype) / f ** 0.5
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm(xs, wg, wi, wo, gs, interpret=True)
    want = ref.moe_gmm(xs, wg, wi, wo, gs)
    assert got.shape == (m, d) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
    np.testing.assert_array_equal(np.asarray(got[m - sizes[-1]:]), 0)
