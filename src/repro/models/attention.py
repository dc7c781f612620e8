"""GQA attention with RoPE / M-RoPE, sliding window, paged/slot KV decode.

Three execution paths (``impl``):
  * ``ref``       — pure jnp (chunked, flash-style memory behaviour via
                    lax.scan over query chunks).  This is the path the
                    multi-pod dry-run lowers (XLA-native, shardable).
  * ``pallas``    — the TPU kernels in ``repro.kernels`` (flash_prefill /
                    paged_attention), compiled for the chip.
  * ``interpret`` — the same kernels in Pallas interpret mode: the CPU
                    tests' stand-in for ``pallas``.

Head-count padding: query heads are padded to a multiple of the TP degree;
KV heads are padded only when ``cfg.kv_shard_mode(tp) == "heads"`` (cost
<= 2x), otherwise the KV cache is sequence-sharded (context-parallel
decode).  Padded heads are real compute (recorded in the roofline's
useful-FLOPs ratio) — the logical model is unchanged.

Sliding-window attention stores a ring-buffer cache of ``window`` slots so
long-context decode reads O(window), not O(S).  The window and the RoPE
scaling are per layer (``cfg.window_at(pos)``, ``cfg.yarn_at(pos)``): a
period may mix window layers, each with its ring, and full layers.

The slot cache is laid out ``(KVp, B, D, S)`` per layer, tokens on the
last (lane) axis, and the model stacks it over layers: ``(P, KVp, B, D,
S)`` *is* the paged kernel's pool as it stands (slot b is row b, holding
pages [b*S/page, (b+1)*S/page)).  Decode writes each new column and
reads its layer in that one buffer, in place.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (ParamBuilder, apply_rope, mrope_cos_sin,
                                 rope_cos_sin)

NEG_INF = -1e30


def init_attention(b: ParamBuilder, cfg, tp: int):
    d = cfg.d_model
    hp = cfg.heads_padded(tp)
    kvp = cfg.kv_heads_padded(tp)
    D = cfg.head_dim
    kv_spec = "model" if cfg.kv_shard_mode(tp) == "heads" else None
    b.param("wq", (d, hp * D), (None, "model"))
    b.param("wk", (d, kvp * D), (None, kv_spec))
    b.param("wv", (d, kvp * D), (None, kv_spec))
    b.param("wo", (hp * D, d), ("model", None))
    if cfg.qkv_bias:
        b.param("bq", (hp * D,), ("model",), init="zeros")
        b.param("bk", (kvp * D,), (kv_spec,), init="zeros")
        b.param("bv", (kvp * D,), (kv_spec,), init="zeros")


def _qkv(params, cfg, x, tp, constrain=None):
    B, S, _ = x.shape
    constrain = constrain or (lambda a, spec: a)
    hp, kvp, D = cfg.heads_padded(tp), cfg.kv_heads_padded(tp), cfg.head_dim
    kv_spec = "model" if cfg.kv_shard_mode(tp) == "heads" else None
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = constrain(q, ("batch", None, "model"))
    k = constrain(k, ("batch", None, kv_spec))
    v = constrain(v, ("batch", None, kv_spec))
    return (q.reshape(B, S, hp, D), k.reshape(B, S, kvp, D),
            v.reshape(B, S, kvp, D))


def _rope(cfg, q, k, positions, pos: int = 0):
    """positions: (B, S) for rope, (B, S, 3) for mrope; ``pos`` the
    layer's place in the period (its RoPE scaling)."""
    if cfg.rope_type == "none":
        return q, k
    if cfg.rope_type == "mrope":
        cos, sin = mrope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    else:
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                yarn=cfg.yarn_at(pos))
    return (apply_rope(q, cos, sin).astype(q.dtype),
            apply_rope(k, cos, sin).astype(k.dtype))


def _gqa_scores(q, k):
    """q (B,Sq,Hq,D), k (B,Sk,Hkv,D) -> scores (B,Hkv,G,Sq,Sk)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / (D ** 0.5)


def _gqa_out(probs, v):
    """probs (B,Hkv,G,Sq,Sk), v (B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    B, Hkv, G, Sq, Sk = probs.shape
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hkv * G, out.shape[-1])


def chunked_causal_attention(q, k, v, *, chunk_q: int = 512,
                             window: Optional[int] = None):
    """Causal (optionally sliding-window) attention, O(chunk_q * S) memory.

    lax.scan over query chunks keeps the peak score tensor at
    (B, H, chunk_q, S) — the XLA analogue of flash attention's memory
    behaviour, so 32K-token prefill fits on chip.
    """
    B, S, Hq, D = q.shape
    cq = min(chunk_q, S)
    if S % cq:
        cq = S  # fallback for tiny/odd shapes
    n_chunks = S // cq
    qc = q.reshape(B, n_chunks, cq, Hq, D).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(S)

    # checkpointed: bwd recomputes each chunk's probs instead of saving
    # (B,H,cq,S) f32 for every chunk — flash-attention memory behaviour
    # in both directions.
    @jax.checkpoint
    def body(_, args):
        i, qi = args
        base = i * cq
        scores = _gqa_scores(qi, k)  # (B,Hkv,G,cq,S)
        qpos = base + jnp.arange(cq)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        return None, _gqa_out(probs.astype(v.dtype), v)

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, Hq, D)


def full_attention(params, cfg, x, positions, tp, *, impl: str = "ref",
                   constrain=None, pos: int = 0):
    """Prefill / train path for layer ``pos`` of the period.  Returns
    (out, (k, v)) — k/v for cache write."""
    q, k, v = _qkv(params, cfg, x, tp, constrain)
    q, k = _rope(cfg, q, k, positions, pos)
    window = cfg.window_at(pos)
    if impl in ("pallas", "interpret"):
        from repro.kernels import ops
        out = ops.flash_prefill(q, k, v, window=window,
                                interpret=impl == "interpret")
    else:
        out = chunked_causal_attention(q, k, v, window=window)
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"]), (k, v)


# ---------------------------------------------------------------------------
# Decode (slot-dense cache; ring buffer under sliding window)
# ---------------------------------------------------------------------------


def cache_shape(cfg, batch: int, max_seq: int, tp: int, pos: int = 0):
    """One layer's slot cache; a window layer keeps a ring of its
    window."""
    w = cfg.window_at(pos)
    S = min(max_seq, w) if w else max_seq
    return (cfg.kv_heads_padded(tp), batch, cfg.head_dim, S)


def decode_attention(params, cfg, x, positions, cache_k, cache_v, layer,
                     seq_lens, tp, *, impl: str = "ref", pos: int = 0):
    """One-token decode step on layer ``layer`` of the stacked cache of
    the period's layer ``pos``.

    x (B, 1, d); positions (B, 1) or (B, 1, 3); cache_k/v
    (P, KVp, B, D, Scache), every layer's; layer: scalar int32; seq_lens
    (B,) = tokens already in cache.  Each slot's new K/V column is
    written at ``(layer, :, b, :, seq_lens[b])`` (``kv_append``, which
    rewrites only the page that holds it; a window layer's ring at
    ``seq_lens[b] % window``) and the kernel reads the layer where it
    lies, so the cache is neither sliced nor copied.
    Returns (out (B,1,d), cache_k, cache_v).
    """
    B = x.shape[0]
    q, k1, v1 = _qkv(params, cfg, x, tp)
    q, k1 = _rope(cfg, q, k1, positions, pos)
    Scache = cache_k.shape[4]
    w = cfg.window_at(pos)
    slot = (seq_lens % w) if w else seq_lens
    if impl in ("pallas", "interpret"):
        from repro.kernels import ops
        interpret = impl == "interpret"
        cache_k, cache_v = ops.append_dense(
            cache_k, cache_v, k1[:, 0], v1[:, 0], layer, slot,
            interpret=interpret)
        out = ops.paged_attention_dense(q[:, 0], cache_k, cache_v, layer,
                                        seq_lens + 1, window=w,
                                        interpret=interpret)
    else:
        bidx = jnp.arange(B)
        cache_k = cache_k.at[layer, :, bidx, :, slot].set(
            k1[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[layer, :, bidx, :, slot].set(
            v1[:, 0].astype(cache_v.dtype))
        ck = jax.lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False)
        Hkv, D = ck.shape[0], ck.shape[2]
        qg = q[:, 0].reshape(B, Hkv, -1, D)
        scores = jnp.einsum("bhgd,hbdk->bhgk", qg, ck) / (D ** 0.5)
        kpos = jnp.arange(Scache)
        if w:
            valid = kpos[None, :] < jnp.minimum(seq_lens + 1, w)[:, None]
        else:
            valid = kpos[None, :] <= seq_lens[:, None]
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bhgk,hbdk->bhgd", probs.astype(cv.dtype), cv)
    out = out.reshape(B, 1, -1)
    return (jnp.einsum("bsh,hd->bsd", out, params["wo"]),
            cache_k, cache_v)


def prefill_into_cache(cache_k, cache_v, k, v, seq_lens=None, window=None):
    """Write a full prompt's K/V into the slot cache (left-aligned).

    cache_k/v (KVp, B, D, Sc); k/v (B, S, KVp, D).  A ring-buffer
    (window) cache keeps only the last ``window`` tokens, at their
    rotated slots: of all S, or with ``seq_lens`` (B,) of each row's
    valid tokens (rows right-padded to S).
    """
    B, S = k.shape[:2]
    k = k.transpose(2, 0, 3, 1)          # (KVp, B, D, S)
    v = v.transpose(2, 0, 3, 1)
    if window:
        # ring slot s holds the last valid position p < n with p % W == s
        n = (jnp.full((B,), S, jnp.int32) if seq_lens is None
             else seq_lens)[:, None]
        W = cache_k.shape[3]
        p = n - 1 - (n - 1 - jnp.arange(W)) % W                 # (B, W)
        ok = (p >= 0)[None, :, None, :]
        take = lambda x: jnp.take_along_axis(
            x, jnp.clip(p, 0, S - 1)[None, :, None, :], axis=3)
        cache_k = jnp.where(ok, take(k), 0).astype(cache_k.dtype)
        cache_v = jnp.where(ok, take(v), 0).astype(cache_v.dtype)
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, 0, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, 0, 0, 0))
    return cache_k, cache_v
