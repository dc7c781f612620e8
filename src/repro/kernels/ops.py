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
import numpy as np

from repro.kernels import flash_prefill as _fp
from repro.kernels import paged_attention as _pa
from repro.kernels import ssm_scan as _ssm
from repro.kernels import unified_pd as _updk

# Pages of a dense slot cache that ``kv_append`` rewrites: this many
# tokens on the lanes, where the slot length is a multiple of it.
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
    """q (B,Hq,D) over paged cache (Hkv,N,D,page) -> (B,Hq,D)."""
    return _pa.paged_attention(q, k_pages, v_pages, block_tables,
                               seq_lens, interpret=interpret)


def dense_page_size(seq: int, page: int = DENSE_PAGE) -> int:
    """``page`` where it divides ``seq``, else the whole slot: a page is
    the lane dim of ``kv_append``'s block, so a multiple of 128 or the
    row."""
    return page if seq % page == 0 else seq


def paged_attention_dense(q, cache_k, cache_v, layer, seq_lens, *,
                          window: Optional[int] = None,
                          interpret: bool = False):
    """Decode attention over layer ``layer`` of a *dense slot* cache via
    the paged kernel.

    q (B,Hq,D); cache_k/v (P,Hkv,B,D,Sc), stacked over P layers; seq_lens
    (B,) valid tokens (for ring-buffer windows pass min(len, window) —
    all slots valid).  The kernel reads the stacked cache where it lies:
    slot b is row b, its pages of ``dense_block_tokens`` tokens
    [b*np, (b+1)*np) in an identity block table, and ``layer`` picks the
    layer.
    """
    B = cache_k.shape[2]
    page = dense_block_tokens(cache_k)
    n_pages = cache_k.shape[4] // page
    tables = (jnp.arange(B)[:, None] * n_pages +
              jnp.arange(n_pages)[None, :]).astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    if window is not None:
        lens = jnp.minimum(lens, window)
    return _pa.paged_attention(q, cache_k, cache_v, tables, lens, layer,
                               page=page, interpret=interpret)


def dense_block_tokens(cache) -> int:
    """Tokens of a slot that one grid step of ``paged_attention_dense``
    reads, for a stacked dense cache (P,Hkv,B,D,Sc) or its shape
    struct."""
    _, Hkv, _, D, Sc = cache.shape
    return _pa.block_tokens(Sc, Hkv, D, jnp.dtype(cache.dtype).itemsize)


def dense_blocks(cache, seq_lens, window: Optional[int] = None) -> tuple:
    """(fetched, spanned): the K/V blocks ``paged_attention_dense``
    copies and those its grid spans over all P layers of a stacked dense
    cache (P,Hkv,B,D,Sc), for host-side ``seq_lens`` (B,) as that
    function takes them.  Blocks past a slot's last live one are not
    fetched."""
    P, _, B, _, Sc = cache.shape
    T = dense_block_tokens(cache)
    n = np.asarray(seq_lens, np.int64)
    if window is not None:
        n = np.minimum(n, window)
    return P * int((-(-n // T)).sum()), P * B * (Sc // T)


def append_dense(cache_k, cache_v, k_new, v_new, layer, slot, *,
                 interpret: bool = False):
    """Write each slot's new K/V column k/v_new (B,Hkv,D) at token
    ``slot[b]`` of layer ``layer`` of a dense slot cache (P,Hkv,B,D,Sc),
    in place (``paged_attention.kv_append``)."""
    page = dense_page_size(cache_k.shape[4])
    return _pa.kv_append(cache_k, cache_v, k_new, v_new, slot, layer,
                         page=page, interpret=interpret)


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
    q_p/k_p/v_p (Bp,S,H,D); q_d (Bd,Hq,D); pages (Hkv,N,page,D) — the
    fused kernel keeps the token-major page layout.
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
