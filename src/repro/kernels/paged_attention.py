"""Paged decode attention — Pallas TPU kernel over block-table KV.

One query token per sequence attends to its paged KV cache.  The block
table and sequence lengths are *scalar-prefetched* (SMEM) so that the
k/v-page BlockSpec index maps can chase the page indirection: the page
streamed into VMEM for grid step (b, h, p) is physical page
``block_tables[b, p]`` — the TPU-native analogue of vLLM's gather, with
no host-side KV reshuffle.

Page layout: ``k_pages``/``v_pages`` are ``(Hkv, N, page, D)`` — KV head
ahead of the page index, so a (page, D) tile is the minor two dims of
the array and the (1, 1, page, D) block satisfies Mosaic's tiling rule
for any page size.  A dense slot cache laid out ``(Hkv, B, S, D)``
reshapes to this layout without a copy (``ops.paged_attention_dense``).

Grid: (B, Hkv, max_pages); the page dim is innermost/sequential, carrying
flash-style (m, l, acc) scratch for the G grouped query heads.

VMEM working set per program (page=128, G=12, D=128):
    q     (G, D)        f32     k/v page (page, D)   bf16
    acc   (G, D)        f32     m, l     (G,)        f32
well under budget.  Out-of-range pages (seq ended) are culled at block
level via @pl.when, so short sequences cost only their own pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page: int, max_pages: int,
                  sm_scale: float):
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
        k = k_ref[0, 0].astype(jnp.float32)            # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
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
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(p == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    interpret: bool = False):
    """q (B,Hq,D); k/v_pages (Hkv,N,page,D); block_tables (B,max_pages)
    int32; seq_lens (B,).  Returns (B,Hq,D)."""
    B, Hq, D = q.shape
    Hkv, N, page, _ = k_pages.shape
    G = Hq // Hkv
    max_pages = block_tables.shape[1]
    # (B, Hkv, G, D) query layout: G grouped heads ride the sublane dim
    qg = q.reshape(B, Hkv, G, D)

    grid = (B, Hkv, max_pages)
    kernel = functools.partial(_paged_kernel, page=page,
                               max_pages=max_pages,
                               sm_scale=1.0 / (D ** 0.5))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, p, tab, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, p, tab, lens: (h, tab[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, p, tab, lens: (h, tab[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, tab, lens: (b, h, 0, 0)),
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
    )(block_tables, seq_lens, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
