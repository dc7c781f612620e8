"""Grouped SwiGLU over one chip's held experts — Pallas TPU kernels.

The expert layer sorts its (token, choice) pairs by held expert and
hands them here as rows ``xs`` (m, d) with ``group_sizes`` (Eh + 1,):
rows ``[off[e], off[e + 1])`` go to held expert e, and the last group
holds the pairs whose expert lives on another chip (or padding).  The
three products of the SwiGLU FFN are grouped matrix products
(``jax.experimental.pallas.ops.tpu.megablox.gmm``):

    h = act(xs @ w_gate[e]) * (xs @ w_in[e]);   out = h @ w_out[e]

Grid ``(n tiles, m tiles visited, k tiles)``: the m-tile steps are only
those that hold rows of a held group (a dynamic grid length), and each
step's weight block is that group's, so an expert that got no pair is
never visited and none of its weight tiles is fetched.  The last group
is outside the ``Eh`` weight banks and is not computed; its rows come
back as zeros.

Tiles: ``tm`` rows (32 for a decode step's few pairs per expert, 256
for prefill), ``tk`` x ``tn`` weight tiles of at most 1152 x 1152
(2304 x 896 expert banks split in two along d), double-buffered: about
7 MB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm


def _tile(dim: int, want: int) -> int:
    """``want`` where it divides ``dim``, else the whole dim."""
    return want if dim % want == 0 else dim


def row_tile(m: int) -> int:
    """Rows per m tile: few for decode's handful of pairs an expert,
    more for prefill; a divisor of ``m``."""
    want = 32 if m <= 1024 else 256
    while m % want:
        want //= 2
    return max(want, 1)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def moe_gmm(xs, w_gate, w_in, w_out, group_sizes, *, act=jax.nn.silu,
            interpret: bool = False):
    """xs (m, d) sorted by group; w_gate/w_in (Eh, d, f); w_out (Eh, f,
    d); group_sizes (Eh + 1,) int32 summing to m; ``act`` the gate's
    activation.  Returns (m, d) in xs's dtype, zero on the last group's
    rows."""
    m, d = xs.shape
    f = w_gate.shape[2]
    tm = row_tile(m)
    up = (tm, _tile(d, 1152), _tile(f, 896))
    down = (tm, _tile(f, 896), _tile(d, 1152))
    mm = functools.partial(gmm, group_sizes=group_sizes,
                           preferred_element_type=xs.dtype,
                           interpret=interpret)
    h = act(mm(xs, w_gate, tiling=up).astype(jnp.float32)) * \
        mm(xs, w_in, tiling=up).astype(jnp.float32)
    return mm(h.astype(xs.dtype), w_out, tiling=down)
