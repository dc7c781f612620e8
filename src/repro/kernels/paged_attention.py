"""Paged decode attention — Pallas TPU kernel over block-table KV.

One query token per sequence attends to its paged KV cache.  The block
table, the sequence lengths and the layer are *scalar-prefetched* (SMEM)
so that the k/v-page BlockSpec index maps can chase the page
indirection: the page streamed into VMEM for grid step (b, h, p) is
physical page ``block_tables[b, p]`` of layer ``layer`` — the
TPU-native analogue of vLLM's gather, with no host-side KV reshuffle.

Page layout: tokens on lanes.  ``k_pages``/``v_pages`` are
``(L, Hkv, R, D, W)``, stacked over L layers (or ``(Hkv, R, D, W)`` for
one), and each row of W tokens holds ``W // page`` pages: physical page
t is lanes ``[(t % npr) * page, (t % npr + 1) * page)`` of row
``t // npr``, npr = W // page.  A page pool is the case W == page.  The
(1, 1, 1, D, page) block is a (D, page) tile with the token axis minor,
which holds any head dim D without padding; page is a multiple of 128
or the whole row.  A dense slot cache ``(L, Hkv, B, D, S)`` is such a
pool as it stands — slot b is row b (``ops.paged_attention_dense``) —
so decode reads every layer's cache in place.

Grid: (B, Hkv, max_pages); the page dim is innermost/sequential, carrying
flash-style (m, l, acc) scratch for the G grouped query heads.  Scores
are ``q (G, D) @ k (D, page)``; the value product ``p (G, page) ·
v (D, page)^T`` is the NT form.

``kv_append`` writes decode's new K/V column into such a stacked cache
in place, page by page, so that the step program never slices, relays
out or writes back a layer's cache.

VMEM working set per program (page=128, G=12, D=128):
    q     (G, D)        f32     k/v page (D, page)   bf16
    acc   (G, D)        f32     m, l     (G,)        f32
well under budget.  Out-of-range pages (seq ended) are culled at block
level via @pl.when, so short sequences cost only their own pages.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, page: int,
                  max_pages: int, sm_scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = lens_ref[b]

    @pl.when(p * page < n)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)         # (D, page)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (G, page)
        pos = p * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < n, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        pexp = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pexp, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(p == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, layer=0,
                    *, page: Optional[int] = None, interpret: bool = False):
    """q (B,Hq,D); k/v_pages (L,Hkv,R,D,W) or (Hkv,R,D,W); block_tables
    (B,max_pages) int32; seq_lens (B,); layer: which of the L layers;
    page: tokens per page (default W).  Returns (B,Hq,D)."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    B, Hq, D = q.shape
    _, Hkv, _, _, W = k_pages.shape
    page = page or W
    npr = W // page
    G = Hq // Hkv
    max_pages = block_tables.shape[1]
    # (B, Hkv, G, D) query layout: G grouped heads ride the sublane dim
    qg = q.reshape(B, Hkv, G, D)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    grid = (B, Hkv, max_pages)
    kernel = functools.partial(_paged_kernel, page=page,
                               max_pages=max_pages,
                               sm_scale=1.0 / (D ** 0.5))

    def kv_page(b, h, p, tab, lens, lay):
        t = tab[b, p]
        return (lay[0], h, t // npr, 0, t % npr)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, p, tab, lens, lay: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, D, page), kv_page),
            pl.BlockSpec((1, 1, 1, D, page), kv_page),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, tab, lens, lay: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens, layer, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)


def _append_kernel(slot_ref, layer_ref, kn_ref, vn_ref, kc_ref, vc_ref,
                   ko_ref, vo_ref, *, page: int):
    b = pl.program_id(0)
    hit = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape, 4) == \
        slot_ref[b] % page
    ko_ref[...] = jnp.where(hit, kn_ref[...][:, :, None], kc_ref[...])
    vo_ref[...] = jnp.where(hit, vn_ref[...][:, :, None], vc_ref[...])


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def kv_append(cache_k, cache_v, k_new, v_new, slot, layer=0, *, page: int,
              interpret: bool = False):
    """Write k/v_new (B, Hkv, D) at token ``slot[b]`` of row b of layer
    ``layer`` of the stacked cache (L, Hkv, B, D, S), in place: grid step
    b reads and writes back only the (Hkv, D, page) page that holds the
    token, and the cache is aliased to the output, so nothing else of it
    is read, written or copied.  ``slot`` is clamped to the row, as a
    dynamic_update_slice clamps its start.  Returns (cache_k, cache_v).
    """
    _, Hkv, B, D, S = cache_k.shape
    kn = k_new.astype(cache_k.dtype)[..., None]        # (B, Hkv, D, 1)
    vn = v_new.astype(cache_v.dtype)[..., None]
    slot = jnp.clip(slot.astype(jnp.int32), 0, S - 1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    new = pl.BlockSpec((1, Hkv, D, 1), lambda b, s, lay: (b, 0, 0, 0))
    blk = pl.BlockSpec((1, Hkv, 1, D, page),
                       lambda b, s, lay: (lay[0], 0, b, 0, s[b] // page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[new, new, blk, blk], out_specs=[blk, blk])
    return pl.pallas_call(
        functools.partial(_append_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
                   jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype)],
        # operands count the two scalar-prefetch arrays
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(slot, layer, kn, vn, cache_k, cache_v)
