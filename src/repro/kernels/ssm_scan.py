"""Chunked selective-scan (Mamba S6) — Pallas TPU kernel.

Grid: (B, num_din_tiles, num_chunks); the chunk dim is innermost and
sequential, carrying the SSM state h (tile_d, ds) in VMEM scratch across
chunks — HBM traffic is O(L·(din+ds)) instead of O(L·din·ds) for the
materialized-state formulation.

VMEM working set per program (chunk=128, tile_d=256, ds=16):
    xs, dt blocks (chunk, tile_d)  f32      ~256 KB
    B, C blocks   (chunk, ds)      f32      tiny
    A tile        (tile_d, ds)     f32      tiny
    h scratch     (tile_d, ds)     f32      tiny
tile_d is a multiple of 128 (lane dim for the (chunk, tile_d) blocks);
ds (=16 for Mamba) rides the minor dim of the small state tensors and is
lane-padded by Mosaic on real hardware — acceptable because the state
tensors are tiny relative to xs/dt (noted hardware adaptation).

Within a chunk the recurrence is a sequential fori_loop (ds-wide FMAs);
across chunks only h persists.  The final state is emitted for decode
continuity (same protocol as the KV cache, DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(xs_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...]                       # (tile_d, ds)

    def _row(ref, t):
        return ref[0, pl.ds(t, 1), :][0]

    def step(t, h):
        dt_t = _row(dt_ref, t)           # (tile_d,)
        x_t = _row(xs_ref, t)            # (tile_d,)
        b_t = _row(b_ref, t)             # (ds,)
        c_t = _row(c_ref, t)             # (ds,)
        a_t = jnp.exp(dt_t[:, None] * A)             # (tile_d, ds)
        h = a_t * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)      # (tile_d,)
        y_ref[0, pl.ds(t, 1), :] = y_t[None]
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "tile_d", "interpret"))
def ssm_scan(xs, dt, A, Bm, Cm, *, chunk: int = 128, tile_d: int = 256,
             interpret: bool = False):
    """xs/dt (B,L,din) f32; A (din,ds) f32; Bm/Cm (B,L,ds) f32.
    Returns y (B,L,din) f32 and final state (B,din,ds) f32.
    (h0 continuation is handled by the ops wrapper via a state-injection
    chunk; the kernel itself starts from h=0.)
    """
    B, L, din = xs.shape
    ds = A.shape[1]
    chunk = min(chunk, L)
    while L % chunk:
        chunk -= 1
    tile_d = min(tile_d, din)
    while din % tile_d:
        tile_d -= 1
    nc, nd = L // chunk, din // tile_d
    grid = (B, nd, nc)

    kernel = functools.partial(_ssm_kernel, chunk=chunk, num_chunks=nc)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, tile_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, tile_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((tile_d, ds), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, tile_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, tile_d, ds), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, din), jnp.float32),
            jax.ShapeDtypeStruct((B, din, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_d, ds), jnp.float32)],
        interpret=interpret,
    )(xs, dt, A, Bm, Cm)
    return y, h_last
