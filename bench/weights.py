"""Random weights from a seed, the same whichever code asks for them.

Every value is a function of ``(seed, leaf path, layer, element index)``
alone: a 32-bit integer hash, turned into an odd integer below 2**24
and scaled by a power of two.  Each of those steps is exact, so the
served model (the whole stacked tree, made in one jitted call on its
device) and the reference (one layer at a time, in float32) read the
same bfloat16 values, on the chip or on the CPU.

The tree's layout is the one the served model keeps: a dict of leaves
whose last key names the weight (``wq``, ``w_in``, ``tok``, ...) and
whose stacked layers sit under ``layers`` with the period index first.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_M32 = 0xFFFFFFFF
MATRICES = ("wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out")
BIASES = ("bq", "bk", "bv")


def _fmix(x: int) -> int:
    """murmur3's 32-bit finaliser on a Python int."""
    x &= _M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & _M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & _M32
    return x ^ (x >> 16)


def _fmix_jnp(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def leaf_keys(seed: int, path: str, layer: int) -> tuple:
    """Two 32-bit keys for one leaf of one layer."""
    tag = zlib.crc32(path.encode())
    k = _fmix(seed & _M32) ^ _fmix((seed >> 32) + 0x9E3779B9)
    k1 = _fmix(k ^ _fmix(tag + 0x632BE5AB))
    k2 = _fmix(k1 ^ _fmix(layer + 0x7F4A7C15))
    return k1, k2


def law(path: str, shape, tied: bool) -> tuple:
    """(mean, amplitude) of the uniform law of one leaf; the amplitude is
    a power of two near sqrt(3) * the standard deviation wanted.  A tied
    embedding is also the head, so it gets the head's 1/sqrt(d): logits
    of unit scale, where rounding can move the first choice."""
    name = path.rsplit("/", 1)[-1]
    parent = path.split("/")[-2] if "/" in path else ""
    if name == "tok":
        std, mean = (1.0 / math.sqrt(shape[-1]) if tied else 1.0), 0.0
    elif name in MATRICES or parent == "lm_head":
        std, mean = 1.0 / math.sqrt(shape[-2]), 0.0
    elif name in BIASES:
        std, mean = 0.1, 0.0
    elif parent.startswith("norm") or parent == "final_norm":
        std, mean = 0.1, 1.0
    else:
        raise KeyError(f"no weight law for leaf {path!r}")
    return mean, 2.0 ** round(math.log2(std * math.sqrt(3.0)))


def _values(shape, k1, k2, mean: float, amp: float, dtype):
    idx = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
    x = _fmix_jnp(_fmix_jnp(idx ^ k1) + k2)
    odd = ((x >> 8).astype(jnp.int32) * 2 + 1 - (1 << 24))
    v = odd.astype(jnp.float32) * jnp.float32(amp / (1 << 24))
    if mean:
        v = v + jnp.float32(mean)
    return v.astype(dtype)


def paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(k.key) for k in p) for p, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def make_params(like, seed: int, sharding):
    """A tree shaped and typed as ``like`` (arrays or ShapeDtypeStructs),
    filled from ``seed`` in one jitted call, placed by ``sharding``."""
    names, leaves, treedef = paths(like)
    tied = "lm_head/w" not in names
    specs = []
    for name, leaf in zip(names, leaves):
        stacked = name.startswith("layers/")
        per = leaf.shape[1:] if stacked else leaf.shape
        n = leaf.shape[0] if stacked else 1
        keys = np.array([leaf_keys(seed, name, i) for i in range(n)],
                        np.uint32)
        specs.append((stacked, tuple(per), keys, *law(name, per, tied),
                      jnp.dtype(leaf.dtype)))

    def build(key_arrays):
        out = []
        for (stacked, per, _, mean, amp, dt), keys in zip(specs, key_arrays):
            one = lambda k: _values(per, k[0], k[1], mean, amp, dt)
            out.append(jax.vmap(one)(keys) if stacked else one(keys[0]))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=sharding)([s[2] for s in specs])


def layer_params(names_shapes, seed: int, layer: int, device=None):
    """Float32 values of one layer's leaves, as ``{path: array}``.
    ``names_shapes`` maps each ``layers/...`` path to (per-layer shape,
    the dtype it is served in); the values are rounded to that dtype."""
    out = {}
    for name, (shape, served) in names_shapes.items():
        k1, k2 = leaf_keys(seed, name, layer)
        mean, amp = law(name, shape, False)
        out[name] = _leaf_f32(tuple(shape), np.uint32(k1), np.uint32(k2),
                              mean, amp, jnp.dtype(served), device)
    return out


def leaf(name: str, shape, served, seed: int, tied: bool, device=None):
    """Float32 values of one unstacked leaf (embedding, head, norm)."""
    k1, k2 = leaf_keys(seed, name, 0)
    mean, amp = law(name, shape, tied)
    return _leaf_f32(tuple(shape), np.uint32(k1), np.uint32(k2), mean, amp,
                     jnp.dtype(served), device)


def _leaf_f32(shape, k1, k2, mean, amp, served, device):
    k = jax.device_put(np.array([k1, k2], np.uint32), device)
    return _leaf_jit(k, shape=shape, mean=mean, amp=amp, served=served)


def _leaf_impl(k, *, shape, mean, amp, served):
    return _values(shape, k[0], k[1], mean, amp, served).astype(jnp.float32)


_leaf_jit = jax.jit(_leaf_impl, static_argnames=("shape", "mean", "amp",
                                                 "served"))
