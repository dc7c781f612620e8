"""Unified P/D attention step — the paper's technique as one Pallas kernel.

RAPID-Serve's CU masking gives prefill and decode disjoint *spatial*
shares of the GPU.  A TPU core timeslices one program, so the spatial
knob becomes a *grid-slot* knob: this kernel issues prefill q-tiles and
decode requests from a single ``pallas_call`` whose slot schedule
interleaves the two kinds at a controllable ratio.  ``f_decode`` — the
Adaptive Resource Manager's control variable — sets how densely decode
slots are packed at the head of the schedule:

    f_decode = 1.0  -> all decode tiles issue first (decode priority;
                       min ITL, prefill waits)
    f_decode = 0.25 -> one decode tile every 4 slots; decode's last tile
                       completes ~4x later, prefill proceeds meanwhile

so decode latency scales ~1/f_decode while prefill throughput scales
~1/(1-f_decode·n_d/n), exactly the trade the paper's Fig 7 sweeps.  Both
phases' tiles live in ONE launch: when decode runs out of tiles, the
remaining slots are all prefill — the overallocation behaviour of Fig 6c
falls out for free (no gaps, no second launch).

Mechanics:
  * a scalar-prefetched descriptor table (n_slots, 7) drives every
    BlockSpec index map: [kind, pb, ph, pkvh, pqi, db, dkvh];
  * grid = (n_slots, n_inner): prefill slots loop k-blocks (flash,
    causal-culled), decode slots loop KV pages (block-table indirection);
  * flash scratch (acc, m, l) is shared — decode uses the first G rows;
  * wrong-kind output windows are routed to a trash block (index Bp/Bd)
    and sliced off, so real blocks are written exactly once.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
PREFILL, DECODE = 0, 1


def build_slot_schedule(n_prefill: int, n_decode: int,
                        f_decode: float) -> np.ndarray:
    """Merged issue order: position of each decode tile i is
    floor(i / f_decode); prefill tiles fill the remaining slots."""
    n = n_prefill + n_decode
    f = min(max(f_decode, 1e-3), 1.0)
    kinds = np.zeros(n, np.int32)
    pos = np.minimum((np.arange(n_decode) / f).astype(np.int64),
                     n - np.arange(n_decode, 0, -1))
    # resolve collisions by shifting right
    used = np.zeros(n, bool)
    for i, p in enumerate(pos):
        p = int(p)
        while used[p]:
            p += 1
        used[p] = True
        kinds[p] = DECODE
    return kinds


def _make_descriptors(Bp: int, Hq: int, nq: int, Bd: int, Hkv: int,
                      G: int, f_decode: float) -> np.ndarray:
    prefill_tiles = [(b, h, h // G, qi) for b in range(Bp)
                     for h in range(Hq) for qi in range(nq)]
    decode_tiles = [(db, dh) for db in range(Bd) for dh in range(Hkv)]
    kinds = build_slot_schedule(len(prefill_tiles), len(decode_tiles),
                                f_decode)
    desc = np.zeros((len(kinds), 7), np.int32)
    ip = id_ = 0
    for s, kind in enumerate(kinds):
        if kind == PREFILL:
            b, h, kvh, qi = prefill_tiles[ip]
            desc[s] = (PREFILL, b, h, kvh, qi, 0, 0)
            ip += 1
        else:
            db, dh = decode_tiles[id_]
            desc[s] = (DECODE, 0, 0, 0, 0, db, dh)
            id_ += 1
    return desc


def _unified_kernel(desc_ref, tab_ref, lens_ref,
                    qp_ref, kp_ref, vp_ref, qd_ref, kpg_ref, vpg_ref,
                    op_ref, od_ref, acc_ref, m_ref, l_ref, *,
                    block_q: int, block_k: int, nk: int, page: int,
                    max_pages: int, n_inner: int, G: int,
                    window: Optional[int], sm_scale: float):
    s = pl.program_id(0)
    j = pl.program_id(1)
    kind = desc_ref[s, 0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ---------------- prefill flash tile ---------------------------------
    qi = desc_ref[s, 4]
    q_start = qi * block_q
    k_start = j * block_k
    p_needed = (kind == PREFILL) & (j < nk) & \
        (k_start <= q_start + block_q - 1)
    if window is not None:
        p_needed &= (k_start + block_k - 1) > (q_start - window)

    @pl.when(p_needed)
    def _prefill():
        q = qp_ref[0, 0].astype(jnp.float32)
        k = kp_ref[0, 0].astype(jnp.float32)
        v = vp_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc *= sm_scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(sc - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    # ---------------- decode paged tile -----------------------------------
    db = desc_ref[s, 5]
    n_valid = lens_ref[db]
    d_needed = (kind == DECODE) & (j < max_pages) & (j * page < n_valid)

    @pl.when(d_needed)
    def _decode():
        q = qd_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = kpg_ref[0, 0].astype(jnp.float32)           # (page, D)
        v = vpg_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc *= sm_scale
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(pos < n_valid, sc, NEG_INF)
        m_prev = m_ref[:G]
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(sc - m_cur[:, None])
        l_ref[:G] = l_ref[:G] * alpha + jnp.sum(p, axis=1)
        acc_ref[:G] = acc_ref[:G] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:G] = m_cur

    # ---------------- finalize --------------------------------------------
    @pl.when((j == n_inner - 1) & (kind == PREFILL))
    def _fin_p():
        l = jnp.maximum(l_ref[...], 1e-30)
        op_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(op_ref.dtype)

    @pl.when((j == n_inner - 1) & (kind == DECODE))
    def _fin_d():
        l = jnp.maximum(l_ref[:G], 1e-30)
        od_ref[0, 0] = (acc_ref[:G] / l[:, None]).astype(od_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "f_decode",
                              "interpret"))
def unified_pd(q_p, k_p, v_p, q_d, k_pages, v_pages, block_tables,
               seq_lens, *, f_decode: float = 0.5,
               window: Optional[int] = None, block_q: int = 512,
               block_k: int = 512, interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """One fused P/D attention step.

    q_p (Bp,Hq,Sp,D), k_p/v_p (Bp,Hkv,Sp,D)        — prefill batch
    q_d (Bd,Hq,D), k/v_pages (Hkv,N,page,D),
    block_tables (Bd,max_pages), seq_lens (Bd,)     — decode batch
    Returns (o_p (Bp,Hq,Sp,D), o_d (Bd,Hq,D)).
    """
    Bp, Hq, Sp, D = q_p.shape
    Hkv = k_p.shape[1]
    G = Hq // Hkv
    Bd = q_d.shape[0]
    _, N, page, _ = k_pages.shape
    max_pages = block_tables.shape[1]

    block_q = min(block_q, Sp)
    block_k = min(block_k, Sp)
    pad = (-Sp) % block_q
    pad_k = (-Sp) % block_k
    if pad or pad_k:
        q_p = jnp.pad(q_p, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_p = jnp.pad(k_p, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v_p = jnp.pad(v_p, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq = Sp + pad
    nq, nk = Sq // block_q, (Sp + pad_k) // block_k
    n_inner = max(nk, max_pages)

    desc = jnp.asarray(_make_descriptors(Bp, Hq, nq, Bd, Hkv, G, f_decode))
    n_slots = desc.shape[0]
    qd_g = q_d.reshape(Bd, Hkv, G, D)

    kernel = functools.partial(
        _unified_kernel, block_q=block_q, block_k=block_k, nk=nk,
        page=page, max_pages=max_pages, n_inner=n_inner, G=G,
        window=window, sm_scale=1.0 / (D ** 0.5))

    def clamp(x, hi):
        return jnp.minimum(x, hi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots, n_inner),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda s, j, d, t, ln: (d[s, 1], d[s, 2],
                                                 d[s, 4], 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda s, j, d, t, ln: (d[s, 1], d[s, 3],
                                                 clamp(j, nk - 1), 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda s, j, d, t, ln: (d[s, 1], d[s, 3],
                                                 clamp(j, nk - 1), 0)),
            pl.BlockSpec((1, 1, G, D),
                         lambda s, j, d, t, ln: (d[s, 5], d[s, 6], 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda s, j, d, t, ln: (
                             d[s, 6], t[d[s, 5], clamp(j, max_pages - 1)],
                             0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda s, j, d, t, ln: (
                             d[s, 6], t[d[s, 5], clamp(j, max_pages - 1)],
                             0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda s, j, d, t, ln: (
                             jnp.where(d[s, 0] == PREFILL, d[s, 1], Bp),
                             d[s, 2], d[s, 4], 0)),
            pl.BlockSpec((1, 1, G, D),
                         lambda s, j, d, t, ln: (
                             jnp.where(d[s, 0] == DECODE, d[s, 5], Bd),
                             d[s, 6], 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((max(block_q, G), D), jnp.float32),
            pltpu.VMEM((max(block_q, G),), jnp.float32),
            pltpu.VMEM((max(block_q, G),), jnp.float32),
        ],
    )
    o_p, o_d = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bp + 1, Hq, Sq, D), q_p.dtype),
            jax.ShapeDtypeStruct((Bd + 1, Hkv, G, D), q_d.dtype),
        ],
        interpret=interpret,
    )(desc, block_tables, seq_lens, q_p, k_p, v_p, qd_g, k_pages, v_pages)
    return o_p[:Bp, :, :Sp], o_d[:Bd].reshape(Bd, Hq, D)
