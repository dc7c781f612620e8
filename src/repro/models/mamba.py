"""Mamba (S6) selective-state-space mixer.

Train/prefill: chunked associative scan — the sequence is processed in
chunks; within a chunk the linear recurrence h_t = a_t * h_{t-1} + b_t is
computed with ``jax.lax.associative_scan`` and the state is carried across
chunks with ``lax.scan``.  Memory is O(chunk * d_inner * d_state) instead
of O(L * d_inner * d_state).

Decode: O(1) single-step state update; recurrent state = (conv window,
SSM state) — this replaces the KV cache for Mamba layers and flows through
the same decode-owned allocation protocol as KV (DESIGN.md §5).

The TPU hot path is the Pallas kernel in repro/kernels/ssm_scan.py; this
module is the shardable XLA reference used by the dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamBuilder


def init_mamba(b: ParamBuilder, cfg):
    m = cfg.mamba
    d, din, R = cfg.d_model, cfg.d_inner, cfg.dt_rank
    b.param("in_proj", (d, 2 * din), (None, "model"))
    b.param("conv_w", (m.d_conv, din), (None, "model"))
    b.param("conv_b", (din,), ("model",), init="zeros")
    b.param("x_proj", (din, R + 2 * m.d_state), ("model", None))
    b.param("dt_proj", (R, din), (None, "model"))
    b.param("dt_bias", (din,), ("model",), init="zeros")
    b.param("A_log", (din, m.d_state), ("model", None),
            init=lambda rng, shape: jnp.log(jnp.broadcast_to(
                jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)),
            dtype=jnp.float32)
    b.param("D", (din,), ("model",), init="ones", dtype=jnp.float32)
    b.param("out_proj", (din, d), ("model", None))


def _ssm_inputs(params, cfg, xs):
    """xs (B, L, din) -> dt (B,L,din), Bm/Cm (B,L,ds) in f32."""
    m = cfg.mamba
    R = cfg.dt_rank
    dbc = jnp.einsum("bld,dr->blr", xs, params["x_proj"])
    dt, Bm, Cm = jnp.split(dbc, [R, R + m.d_state], axis=-1)
    dt = jnp.einsum("blr,rd->bld", dt, params["dt_proj"]) + params["dt_bias"]
    dt = jax.nn.softplus(dt.astype(jnp.float32))
    return dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32)


def _causal_conv(params, cfg, x, conv_state=None):
    """Depthwise causal conv.  x (B, L, din)."""
    m = cfg.mamba
    w = params["conv_w"]  # (d_conv, din)
    if conv_state is not None:
        x = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    else:
        x = jnp.pad(x, ((0, 0), (m.d_conv - 1, 0), (0, 0)))
    out = sum(x[:, i:i + x.shape[1] - m.d_conv + 1] * w[i]
              for i in range(m.d_conv))
    return out + params["conv_b"]


def mamba_forward(params, cfg, x, *, chunk: int = 256, state=None,
                  return_state: bool = False, impl: str = "ref",
                  constrain=None):
    """x (B, L, d_model) -> (B, L, d_model).

    ``state``: optional dict(conv (B, d_conv-1, din), ssm (B, din, ds)).
    """
    m = cfg.mamba
    constrain = constrain or (lambda a, spec: a)
    B, L, _ = x.shape
    din = cfg.d_inner
    xz = jnp.einsum("bld,dk->blk", x, params["in_proj"])
    xz = constrain(xz, ("batch", None, "model"))  # keep din TP-sharded
    xs, z = jnp.split(xz, 2, axis=-1)
    conv_state = state["conv"] if state is not None else None
    xs = jax.nn.silu(_causal_conv(params, cfg, xs, conv_state))
    xs = constrain(xs, ("batch", None, "model"))
    dt, Bm, Cm = _ssm_inputs(params, cfg, xs)
    dt = constrain(dt, ("batch", None, "model"))
    A = -jnp.exp(params["A_log"])  # (din, ds)

    if impl in ("pallas", "interpret"):
        from repro.kernels import ops
        h0 = state["ssm"] if state is not None else None
        y, h_last = ops.ssm_scan(xs.astype(jnp.float32), dt, A, Bm, Cm,
                                 h0=h0, interpret=impl == "interpret")
    else:
        y, h_last = ssm_scan_ref(xs.astype(jnp.float32), dt, A, Bm, Cm,
                                 chunk=chunk,
                                 h0=state["ssm"] if state is not None
                                 else None)
    y = y + xs.astype(jnp.float32) * params["D"]
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = jnp.einsum("bld,dk->blk", y, params["out_proj"])
    if return_state:
        tail = xz[:, L - (m.d_conv - 1):, :din] if L >= m.d_conv - 1 else None
        new_state = {
            "conv": _conv_tail(params, cfg, state, xz[..., :din]),
            "ssm": h_last,
        }
        return out, new_state
    return out


def _conv_tail(params, cfg, state, xs_raw):
    """Last (d_conv - 1) pre-activation conv inputs, for decode continuity."""
    m = cfg.mamba
    k = m.d_conv - 1
    B, L, din = xs_raw.shape
    if state is not None:
        full = jnp.concatenate([state["conv"].astype(xs_raw.dtype), xs_raw],
                               axis=1)
    else:
        full = jnp.pad(xs_raw, ((0, 0), (k, 0), (0, 0)))
    return full[:, full.shape[1] - k:]


def ssm_scan_ref(xs, dt, A, Bm, Cm, *, chunk: int = 256, h0=None):
    """Chunked associative scan for h_t = a_t h_{t-1} + b_t; y_t = C_t.h_t.

    xs/dt (B,L,din) f32; A (din,ds); Bm/Cm (B,L,ds).
    Returns y (B,L,din) f32 and final state (B,din,ds).

    The chunk body is jax.checkpoint'ed: scan-AD then saves only the
    per-chunk carry h (B,din,ds — tiny) instead of the (B,c,din,ds)
    prefix-product tensors for EVERY chunk, which at jamba train scale
    is ~8.6 GB/chip/layer (dry-run §Perf log).
    """
    B, L, din = xs.shape
    ds = A.shape[1]
    c = min(chunk, L)
    while L % c:
        c -= 1
    nc = L // c

    def reshape(t):
        return t.reshape(B, nc, c, *t.shape[2:]).transpose(1, 0, 2,
                                                           *range(3, t.ndim + 1))

    xs_c, dt_c, B_c, C_c = map(reshape, (xs, dt, Bm, Cm))
    h_init = h0.astype(jnp.float32) if h0 is not None else \
        jnp.zeros((B, din, ds), jnp.float32)

    @jax.checkpoint
    def chunk_body(h, args):
        xc, dc, bc, cc = args  # (B,c,din), (B,c,din), (B,c,ds), (B,c,ds)
        a = jnp.exp(dc[..., None] * A)            # (B,c,din,ds)
        b = (dc * xc)[..., None] * bc[:, :, None]  # (B,c,din,ds)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        A_pref, B_pref = jax.lax.associative_scan(combine, (a, b), axis=1)
        h_t = A_pref * h[:, None] + B_pref        # (B,c,din,ds)
        y = jnp.einsum("bcds,bcs->bcd", h_t, cc)
        return h_t[:, -1], y

    h_last, ys = jax.lax.scan(chunk_body, h_init, (xs_c, dt_c, B_c, C_c))
    y = ys.transpose(1, 0, 2, 3).reshape(B, L, din)
    return y, h_last


def mamba_decode_step(params, cfg, x, state):
    """Single-token decode.  x (B, 1, d); state {conv (B,k,din), ssm}."""
    m = cfg.mamba
    B = x.shape[0]
    din = cfg.d_inner
    xz = jnp.einsum("bld,dk->blk", x, params["in_proj"])
    xs_raw, z = jnp.split(xz, 2, axis=-1)          # (B,1,din)
    conv_in = jnp.concatenate([state["conv"].astype(x.dtype), xs_raw], axis=1)
    w = params["conv_w"]
    xs = sum(conv_in[:, i] * w[i] for i in range(m.d_conv)) + params["conv_b"]
    xs = jax.nn.silu(xs)[:, None]                   # (B,1,din)
    dt, Bm, Cm = _ssm_inputs(params, cfg, xs)
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt[:, 0, :, None] * A)              # (B,din,ds)
    b = (dt[:, 0] * xs[:, 0].astype(jnp.float32))[..., None] * \
        Bm[:, 0, None]
    h = a * state["ssm"] + b
    y = jnp.einsum("bds,bs->bd", h, Cm[:, 0])
    y = y + xs[:, 0].astype(jnp.float32) * params["D"]
    y = y[:, None].astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bld,dk->blk", y, params["out_proj"])
    new_state = {"conv": conv_in[:, 1:], "ssm": h}
    return out, new_state
