"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

No chunking, no tiling, no flash tricks — the simplest correct math, used
by tests/test_kernels.py to validate the kernels across shape/dtype sweeps
(interpret=True on CPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def causal_attention(q, k, v, *, window: Optional[int] = None,
                     q_offset: int = 0):
    """q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D) -> (B,Hq,Sq,D).  GQA by repeat.

    Query position i (absolute q_offset + i) attends to keys <= its
    position, and within `window` when set.
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (D ** 0.5)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(k.shape[2])
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens):
    """Decode attention over a paged KV cache.

    q (B,Hq,D); k/v_pages (Hkv, N, page, D); block_tables (B, max_pages)
    int32; seq_lens (B,) = valid tokens per sequence (including the
    current token, already written to its slot).  Returns (B,Hq,D).
    """
    B, Hq, D = q.shape
    Hkv, N, page, _ = k_pages.shape
    G = Hq // Hkv
    max_pages = block_tables.shape[1]

    def one(qb, tab, n):
        # gather this sequence's pages -> (Hkv, max_pages*page, D)
        kk = k_pages[:, tab].reshape(Hkv, max_pages * page, D)
        vv = v_pages[:, tab].reshape(Hkv, max_pages * page, D)
        qg = qb.reshape(Hkv, G, D).astype(jnp.float32)
        scores = jnp.einsum("hgd,hkd->hgk", qg,
                            kk.astype(jnp.float32)) / (D ** 0.5)
        valid = jnp.arange(max_pages * page) < n
        scores = jnp.where(valid[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hgk,hkd->hgd", probs, vv.astype(jnp.float32))
        return out.reshape(Hq, D)

    return jax.vmap(one)(q, block_tables, seq_lens).astype(q.dtype)


def ssm_scan(xs, dt, A, Bm, Cm, h0=None):
    """Sequential (token-by-token) selective scan — the slow exact oracle.

    xs/dt (B,L,din) f32; A (din,ds); Bm/Cm (B,L,ds) f32.
    Returns y (B,L,din) f32, h_last (B,din,ds) f32.
    """
    B, L, din = xs.shape
    ds = A.shape[1]
    h = h0.astype(jnp.float32) if h0 is not None else \
        jnp.zeros((B, din, ds), jnp.float32)

    def step(h, args):
        x_t, dt_t, B_t, C_t = args  # (B,din),(B,din),(B,ds),(B,ds)
        a = jnp.exp(dt_t[..., None] * A)
        b = (dt_t * x_t)[..., None] * B_t[:, None]
        h = a * h + b
        y = jnp.einsum("bds,bs->bd", h, C_t)
        return h, y

    h_last, ys = jax.lax.scan(
        step, h, (xs.transpose(1, 0, 2), dt.transpose(1, 0, 2),
                  Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2), h_last


def unified_pd(q_p, k_p, v_p, q_d, k_pages, v_pages, block_tables,
               seq_lens, *, window: Optional[int] = None):
    """Oracle for the unified P/D step: prefill flash output + decode
    paged output, computed independently (they share no data)."""
    o_p = causal_attention(q_p, k_p, v_p, window=window)
    o_d = paged_attention(q_d, k_pages, v_pages, block_tables, seq_lens)
    return o_p, o_d
