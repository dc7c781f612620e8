"""Jit'd model-facing wrappers around the Pallas kernels.

The models pass (B, S, H, D)-layout tensors; the kernels want
(B, H, S, D).  Every wrapper compiles the kernel for the TPU unless the
caller passes ``interpret=True`` — which only the CPU tests do
(``impl="interpret"`` in the models).  Nothing here looks at the
backend: a chip path that lost its chip fails instead of quietly
interpreting.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import flash_prefill as _fp
from repro.kernels import paged_attention as _pa
from repro.kernels import ssm_scan as _ssm
from repro.kernels import unified_pd as _updk

# Pages of a dense slot cache: a multiple of 16 rows (a bf16 sublane
# tile), at most this many tokens.
DENSE_PAGE = 128


def flash_prefill(q, k, v, *, window: Optional[int] = None,
                  block_q: int = 512, block_k: int = 512,
                  interpret: bool = False):
    """q (B,S,Hq,D), k/v (B,S,Hkv,D) -> (B,S,Hq,D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    S = q.shape[1]
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, S))
    o = _fp.flash_prefill(qt, kt, vt, window=window, block_q=bq,
                          block_k=bk, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    interpret: bool = False):
    """q (B,Hq,D) over paged cache (Hkv,N,page,D) -> (B,Hq,D)."""
    return _pa.paged_attention(q, k_pages, v_pages, block_tables,
                               seq_lens, interpret=interpret)


def dense_page_size(seq: int, page: int = DENSE_PAGE) -> int:
    """Largest multiple of 16 that is <= ``page`` and divides ``seq``."""
    for p in range(min(page, seq) // 16 * 16, 0, -16):
        if seq % p == 0:
            return p
    raise ValueError(
        f"a dense cache of {seq} slots cannot be paged: no multiple of 16 "
        f"up to {page} divides it (size max_seq_len to a multiple of 16)")


def paged_attention_dense(q, cache_k, cache_v, seq_lens, *,
                          window: Optional[int] = None,
                          page: int = DENSE_PAGE, interpret: bool = False):
    """Decode attention over a *dense slot* cache via the paged kernel.

    q (B,Hq,D); cache_k/v (Hkv,B,Sc,D); seq_lens (B,) valid tokens
    (for ring-buffer windows pass min(len, window) — all slots valid).
    The dense cache is viewed as trivially-paged without a copy: slot b
    owns pages [b*np, (b+1)*np), identity block table.
    """
    Hkv, B, Sc, D = cache_k.shape
    page = dense_page_size(Sc, page)
    n_pages = Sc // page
    kp = cache_k.reshape(Hkv, B * n_pages, page, D)
    vp = cache_v.reshape(Hkv, B * n_pages, page, D)
    tables = (jnp.arange(B)[:, None] * n_pages +
              jnp.arange(n_pages)[None, :]).astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    if window is not None:
        lens = jnp.minimum(lens, window)
    return _pa.paged_attention(q, kp, vp, tables, lens, interpret=interpret)


def ssm_scan(xs, dt, A, Bm, Cm, *, h0=None, chunk: int = 128,
             tile_d: int = 256, interpret: bool = False):
    """Chunked selective scan.  h0 continuation falls back to the jnp
    reference (state injection is not expressible as a rank-1 step; only
    the serving chunked-prefill path needs it)."""
    if h0 is not None:
        from repro.kernels import ref
        return ref.ssm_scan(xs, dt, A, Bm, Cm, h0=h0)
    return _ssm.ssm_scan(xs, dt, A, Bm, Cm, chunk=chunk, tile_d=tile_d,
                         interpret=interpret)


def unified_pd(q_p, k_p, v_p, q_d, k_pages, v_pages, block_tables,
               seq_lens, *, f_decode: float = 0.5,
               window: Optional[int] = None, block_q: int = 512,
               block_k: int = 512, interpret: bool = False):
    """Fused concurrent P/D attention step (layouts as models produce):
    q_p/k_p/v_p (Bp,S,H,D); q_d (Bd,Hq,D); pages (Hkv,N,page,D).
    Returns (o_p (Bp,S,Hq,D), o_d (Bd,Hq,D))."""
    Sp = q_p.shape[1]
    bq = min(block_q, max(8, Sp))
    bk = min(block_k, max(8, Sp))
    o_p, o_d = _updk.unified_pd(
        q_p.transpose(0, 2, 1, 3), k_p.transpose(0, 2, 1, 3),
        v_p.transpose(0, 2, 1, 3), q_d, k_pages, v_pages, block_tables,
        seq_lens, f_decode=f_decode, window=window, block_q=bq,
        block_k=bk, interpret=interpret)
    return o_p.transpose(0, 2, 1, 3), o_d
