"""Primitive layers: norms, dense, embeddings, RoPE / M-RoPE, activations.

All parameter creation goes through a ``ParamBuilder`` so that every leaf is
born with a logical PartitionSpec; the spec tree always matches the param
tree structurally (asserted in tests).

Logical sharding axes used below (translated to mesh axes in sharding.py):
  "model"  -> tensor-parallel axis
  "data"   -> ZeRO / batch axis (params: only opt-state dim0)
  None     -> replicated
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


class ParamBuilder:
    """Builds a params pytree and a parallel logical-spec pytree."""

    def __init__(self, rng: jax.Array, dtype=jnp.bfloat16):
        self._rng = rng
        self.dtype = dtype
        self.params: dict = {}
        self.specs: dict = {}

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def scope(self, name: str) -> "ParamBuilder":
        child = ParamBuilder.__new__(ParamBuilder)
        child._rng = self._next_rng()
        child.dtype = self.dtype
        child.params = self.params.setdefault(name, {})
        child.specs = self.specs.setdefault(name, {})
        return child

    def param(self, name: str, shape, spec, init="normal", scale=None,
              dtype=None):
        dtype = dtype or self.dtype
        if init == "normal":
            scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
            arr = (jax.random.normal(self._next_rng(), shape, jnp.float32)
                   * scale).astype(dtype)
        elif init == "zeros":
            arr = jnp.zeros(shape, dtype)
        elif init == "ones":
            arr = jnp.ones(shape, dtype)
        elif callable(init):
            arr = init(self._next_rng(), shape).astype(dtype)
        else:
            raise ValueError(init)
        self.params[name] = arr
        self.specs[name] = spec
        return arr


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


@jax.custom_vjp
def grad_barrier(xs):
    """``optimization_barrier`` with a straight-through gradient.

    ``jax.lax.optimization_barrier`` has no differentiation rule; the
    scan bodies barrier (layer_params, carry) to stop LICM hoisting f32
    upcasts of the whole stacked weights out of the loop, and that sits
    on the grad path of every train step.  The barrier is semantically
    the identity, so the VJP is the identity too — the CSE/LICM-blocking
    effect is preserved on the forward (primal) computation.
    """
    return jax.lax.optimization_barrier(xs)


def _grad_barrier_fwd(xs):
    return jax.lax.optimization_barrier(xs), None


def _grad_barrier_bwd(_, cts):
    return (cts,)


grad_barrier.defvjp(_grad_barrier_fwd, _grad_barrier_bwd)


def rmsnorm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * w


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_cos_sin(positions, head_dim: int, theta: float, dtype=jnp.float32,
                 yarn=None):
    """positions (..., S) -> cos/sin (..., S, head_dim//2).  ``yarn`` (a
    ``YarnConfig``) rescales the frequencies and cos and sin."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    scale = 1.0
    if yarn is not None:
        freqs, scale = yarn_parameters(head_dim, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return ((jnp.cos(ang) * scale).astype(dtype),
            (jnp.sin(ang) * scale).astype(dtype))


def yarn_parameters(head_dim: int, theta: float, yarn):
    """YaRN's inverse frequencies (head_dim // 2,) and cos/sin scale.

    Each frequency blends its extrapolated value (unchanged) with its
    interpolated one (divided by ``factor``) by a linear ramp between the
    dims whose wavelength turns ``beta_fast`` and ``beta_slow`` times
    over the original context: dims below the ramp keep their
    frequency, dims above it are interpolated.
    """
    def correction_dim(rotations):
        return (head_dim * math.log(
            yarn.original_max_position / (rotations * 2 * math.pi))) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    half = head_dim // 2
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))
    inv = base / yarn.factor * ramp + base * (1.0 - ramp)
    scale = yarn.attention_factor
    if scale is None:
        scale = 0.1 * math.log(yarn.factor) + 1.0
    return inv, scale


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # add head dim
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def mrope_cos_sin(positions3, head_dim: int, theta: float,
                  sections=(2, 1, 1), dtype=jnp.float32):
    """M-RoPE (Qwen2-VL): positions3 (..., S, 3) = (t, h, w) ids.

    The rotary half-dim is split into `sections` (proportional) chunks; each
    chunk rotates with its own position stream.  For text tokens the three
    streams coincide and this reduces to standard RoPE.
    """
    half = head_dim // 2
    tot = sum(sections)
    sizes = [half * s // tot for s in sections]
    sizes[-1] = half - sum(sizes[:-1])
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    coss, sins = [], []
    off = 0
    for i, sz in enumerate(sizes):
        pos = positions3[..., i]
        ang = pos[..., None].astype(jnp.float32) * freqs[off:off + sz]
        coss.append(jnp.cos(ang))
        sins.append(jnp.sin(ang))
        off += sz
    return (jnp.concatenate(coss, -1).astype(dtype),
            jnp.concatenate(sins, -1).astype(dtype))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embed(b: ParamBuilder, cfg):
    eb = b.scope("embed")
    eb.param("tok", (cfg.vocab_padded, cfg.d_model), ("model", None),
             scale=1.0)
    if not cfg.tie_embeddings:
        hb = b.scope("lm_head")
        hb.param("w", (cfg.d_model, cfg.vocab_padded), (None, "model"))
    fb = b.scope("final_norm")
    fb.param("w", (cfg.d_model,), (None,), init="ones")


def embed_tokens(params, cfg, tokens):
    return jnp.take(params["embed"]["tok"], tokens, axis=0)


def lm_logits(params, cfg, x):
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].T
    else:
        w = params["lm_head"]["w"]
    x = rmsnorm(x, params["final_norm"]["w"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, w)
