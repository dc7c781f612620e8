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
long-context decode reads O(window), not O(S).

The slot cache is laid out ``(KVp, B, S, D)`` per layer: KV head first,
so it *is* the paged kernel's ``(Hkv, N, page, D)`` pool after a free
reshape (slot b owns pages [b*S/page, (b+1)*S/page)).
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


def _rope(cfg, q, k, positions):
    """positions: (B, S) for rope, (B, S, 3) for mrope."""
    if cfg.rope_type == "none":
        return q, k
    if cfg.rope_type == "mrope":
        cos, sin = mrope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    else:
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
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
                   constrain=None):
    """Prefill / train path.  Returns (out, (k, v)) — k/v for cache write."""
    q, k, v = _qkv(params, cfg, x, tp, constrain)
    q, k = _rope(cfg, q, k, positions)
    if impl in ("pallas", "interpret"):
        from repro.kernels import ops
        out = ops.flash_prefill(q, k, v, window=cfg.sliding_window,
                                interpret=impl == "interpret")
    else:
        out = chunked_causal_attention(q, k, v, window=cfg.sliding_window)
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"]), (k, v)


# ---------------------------------------------------------------------------
# Decode (slot-dense cache; ring buffer under sliding window)
# ---------------------------------------------------------------------------


def cache_shape(cfg, batch: int, max_seq: int, tp: int):
    S = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    return (cfg.kv_heads_padded(tp), batch, S, cfg.head_dim)


def decode_attention(params, cfg, x, positions, cache_k, cache_v, seq_lens,
                     tp, *, impl: str = "ref"):
    """One-token decode step.

    x (B, 1, d); positions (B, 1) or (B, 1, 3); cache_k/v
    (KVp, B, Scache, D); seq_lens (B,) = tokens already in cache.
    Returns (out (B,1,d), cache_k, cache_v).
    """
    B = x.shape[0]
    q, k1, v1 = _qkv(params, cfg, x, tp)
    q, k1 = _rope(cfg, q, k1, positions)
    Scache = cache_k.shape[2]
    w = cfg.sliding_window
    slot = (seq_lens % w) if w else seq_lens
    bidx = jnp.arange(B)
    # k1/v1 (B,1,KVp,D) -> (KVp,B,D) rows written at each slot's position
    cache_k = cache_k.at[:, bidx, slot].set(
        k1[:, 0].transpose(1, 0, 2).astype(cache_k.dtype))
    cache_v = cache_v.at[:, bidx, slot].set(
        v1[:, 0].transpose(1, 0, 2).astype(cache_v.dtype))

    if impl in ("pallas", "interpret"):
        from repro.kernels import ops
        out = ops.paged_attention_dense(q[:, 0], cache_k, cache_v,
                                        seq_lens + 1, window=w,
                                        interpret=impl == "interpret")
    else:
        Hkv, D = cache_k.shape[0], cache_k.shape[3]
        qg = q[:, 0].reshape(B, Hkv, -1, D)
        scores = jnp.einsum("bhgd,hbkd->bhgk", qg, cache_k) / (D ** 0.5)
        kpos = jnp.arange(Scache)
        if w:
            valid = kpos[None, :] < jnp.minimum(seq_lens + 1, w)[:, None]
        else:
            valid = kpos[None, :] <= seq_lens[:, None]
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bhgk,hbkd->bhgd", probs.astype(cache_v.dtype),
                         cache_v)
    out = out.reshape(B, 1, -1)
    return (jnp.einsum("bsh,hd->bsd", out, params["wo"]),
            cache_k, cache_v)


def prefill_into_cache(cache_k, cache_v, k, v, seq_lens=None, window=None):
    """Write a full prompt's K/V into the slot cache (left-aligned).

    cache_k/v (KVp, B, Sc, D); k/v (B, S, KVp, D).  With a ring-buffer
    (window) cache only the last ``window`` tokens are kept, at their
    rotated slots.
    """
    S = k.shape[1]
    k = k.transpose(2, 0, 1, 3)          # (KVp, B, S, D)
    v = v.transpose(2, 0, 1, 3)
    if window:
        W = cache_k.shape[2]
        take = min(S, W)
        src_pos = jnp.arange(take) + max(S - W, 0)
        slots = src_pos % W
        cache_k = cache_k.at[:, :, slots].set(
            k[:, :, max(S - W, 0):].astype(cache_k.dtype))
        cache_v = cache_v.at[:, :, slots].set(
            v[:, :, max(S - W, 0):].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, 0, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, 0, 0, 0))
    return cache_k, cache_v
