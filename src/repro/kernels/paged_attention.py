"""Paged decode attention — Pallas TPU kernel over block-table KV.

One query token per sequence attends to its paged KV cache.  The block
table, the sequence lengths and the layer are *scalar-prefetched* (SMEM)
so that the k/v BlockSpec index maps can chase the page indirection:
grid step (b, j) streams page ``block_tables[b, j]`` of layer ``layer``,
for every KV head at once — the TPU-native analogue of vLLM's gather,
with no host-side KV reshuffle.

Page layout: tokens on lanes.  ``k_pages``/``v_pages`` are
``(L, Hkv, R, D, W)``, stacked over L layers (or ``(Hkv, R, D, W)`` for
one), and each row of W tokens holds ``W // page`` pages: physical page
t is lanes ``[(t % npr) * page, (t % npr + 1) * page)`` of row
``t // npr``, npr = W // page.  A page pool is the case W == page.  The
(1, Hkv, 1, D, page) block is a (D, page) tile per head with the token
axis minor, which holds any head dim D without padding; page is a
multiple of 128 or the whole row.  A dense slot cache ``(L, Hkv, B, D,
S)`` is such a pool as it stands — slot b is row b, its pages of
``block_tokens`` tokens in an identity table
(``ops.paged_attention_dense``) — so decode reads every layer's cache
in place.

Grid: (B, max_pages); the page dim is innermost/sequential, carrying
flash-style (m, l, acc) scratch for every KV head and its G grouped
query heads.  The index map clamps a page past the sequence's end to its
last live page, so a dead step repeats the block index before it and
costs no copy; ``pl.when`` skips its compute.  Scores ``q (G, D) @ k (D,
page)`` per head run on the MXU in the operands' dtype with f32
accumulation (a product of two bf16 values is exact in f32); the value
product ``p (G, page) · v (D, page)^T`` keeps p in f32, as do the
softmax statistics.

``kv_append`` writes decode's new K/V column into such a stacked cache
in place, page by page, so that the step program never slices, relays
out or writes back a layer's cache.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# a grid step reads at most this much K and V of one slot, all heads...
BLOCK_BYTES = 1 << 20
# ... and at most this many tokens: a free slot still reads one page a
# layer, and the (G, page) f32 scores stay a few vregs a head
MAX_BLOCK = 512


def block_tokens(row: int, kv_heads: int, head_dim: int,
                 itemsize: int) -> int:
    """Tokens of one slot that a grid step of ``paged_attention`` reads
    over a dense cache whose rows hold ``row`` tokens: the largest of
    512, 256 that divides the row with K and V of every head within
    ``BLOCK_BYTES``, else 128 — enough bytes a step to hide the step's
    fixed cost — or the whole row where 128 does not divide it."""
    if row % LANES:
        return row
    per_token = 2 * kv_heads * head_dim * itemsize
    for t in (MAX_BLOCK, MAX_BLOCK // 2):
        if row % t == 0 and t * per_token <= BLOCK_BYTES:
            return t
    return LANES


def kv_page(b, j, tab, lens, lay, *, page: int, per_row: int):
    """Index of grid step (b, j)'s K/V block in the stacked cache: the
    sequence's j-th page, clamped to its last live one, so a dead step
    repeats the block before it and costs no copy."""
    last = jnp.maximum(lens[b] - 1, 0) // page
    t = tab[b, jnp.minimum(j, last)]
    return (lay[0], 0, t // per_row, 0, t % per_row)


def _paged_kernel(tables_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, page: int,
                  max_pages: int, sm_scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = lens_ref[b]

    @pl.when(j * page < n)
    def _compute():
        q = q_ref[0]                                    # (Hkv, G, D)
        k = k_ref[0, :, 0]                              # (Hkv, D, page)
        v = v_ref[0, :, 0].astype(jnp.float32)
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # (Hkv, G, page)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < n, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        pexp = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=2,
                                                  keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)         # (Hkv, G, D)

    @pl.when(j == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, layer=0,
                    *, page: Optional[int] = None, interpret: bool = False):
    """q (B,Hq,D); k/v_pages (L,Hkv,R,D,W) or (Hkv,R,D,W); block_tables
    (B,max_pages) int32; seq_lens (B,); layer: which of the L layers;
    page: tokens per page (default W), a grid step's block.  Returns
    (B,Hq,D)."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    B, Hq, D = q.shape
    _, Hkv, _, _, W = k_pages.shape
    page = page or W
    G = Hq // Hkv
    max_pages = block_tables.shape[1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # (B, Hkv, G, D): G grouped heads ride the sublane dim
    qg = q.reshape(B, Hkv, G, D)
    qblk = (1, Hkv, G, D)

    def q_map(b, j, tab, lens, lay):
        return (b, 0, 0, 0)

    kv = pl.BlockSpec((1, Hkv, 1, D, page), functools.partial(
        kv_page, page=page, per_row=W // page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_pages),
        in_specs=[pl.BlockSpec(qblk, q_map), kv, kv],
        out_specs=pl.BlockSpec(qblk, q_map),
        scratch_shapes=[pltpu.VMEM((Hkv, G, D), jnp.float32),
                        pltpu.VMEM((Hkv, G, 1), jnp.float32),
                        pltpu.VMEM((Hkv, G, 1), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, page=page,
                               max_pages=max_pages,
                               sm_scale=1.0 / (D ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
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
