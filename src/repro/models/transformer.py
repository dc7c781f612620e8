"""Composable decoder assembly: layer_pattern x ffn_pattern over periods.

The model is a stack of ``num_layers`` blocks.  Blocks repeat with period
``cfg.period`` (lcm of the mixer and FFN patterns); parameters of repeated
periods are stacked on a leading axis and the forward pass is a
``lax.scan`` over periods (compile-time O(period), not O(num_layers) — a
94-layer qwen3-moe compiles as one 2-layer group scanned 47 times).

Block structure (pre-norm residual):
    x = x + mixer(rmsnorm(x))          mixer in {attn, mamba, mlstm, slstm}
    x = x + ffn(rmsnorm(x))            ffn in {dense, moe, none}
xLSTM mixers carry their own up/down projections, so xlstm archs use
ffn_pattern=("none",).

Two entry points:
  * ``forward``        — train / prefill over a full sequence.  With
                         ``return_aux=True`` also returns per-layer KV (attn)
                         or final recurrent state (mamba/xlstm) for cache
                         population — the serving prefill path.
  * ``decode_forward`` — one-token step against per-layer caches/states.

A layer's attention window and RoPE scaling follow its place in the
period (``cfg.window_at``, ``cfg.yarn_at``); an expert layer that holds
one chip's share (``MoEConfig.held``) also reports the pairs each held
expert computed (``aux["route"]``, ``decode_forward(return_routing=)``).

``inputs`` is either int32 tokens (B, S) or, for ``frontend='embed_stub'``
archs (audio/VLM backbones), precomputed float embeddings (B, S, d_model).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (ParamBuilder, embed_tokens, grad_barrier,
                                 init_embed, lm_logits, rmsnorm)

NEG_INF = -1e30

Constrain = Callable[[jax.Array, tuple], jax.Array]
_IDENTITY: Constrain = lambda a, spec: a


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_block(b: ParamBuilder, cfg, pos: int, tp: int):
    mixer = cfg.mixer_at(pos)
    b.scope("norm1").param("w", (cfg.d_model,), (None,), init="ones")
    mb = b.scope("mixer")
    if mixer == "attn":
        attn_mod.init_attention(mb, cfg, tp)
    elif mixer == "mamba":
        mamba_mod.init_mamba(mb, cfg)
    elif mixer == "mlstm":
        xlstm_mod.init_mlstm(mb, cfg)
    elif mixer == "slstm":
        xlstm_mod.init_slstm(mb, cfg)
    else:
        raise ValueError(mixer)
    ffn = cfg.ffn_at(pos)
    if ffn != "none":
        b.scope("norm2").param("w", (cfg.d_model,), (None,), init="ones")
        fb = b.scope("ffn")
        if ffn == "dense":
            moe_mod.init_dense_ffn(fb, cfg)
        elif ffn == "moe":
            moe_mod.init_moe(fb, cfg, tp)
        else:
            raise ValueError(ffn)


def init_model(rng: jax.Array, cfg, tp: int = 1):
    """Returns (params, logical_spec_tree); structurally identical trees.

    Layer params are stacked over periods: every leaf under ``layers`` has
    leading dim ``cfg.num_periods`` (spec axis None — FSDP shards a dim
    inside the original shape, see sharding.py).
    """
    dtype = jnp.dtype(cfg.dtype)
    b = ParamBuilder(rng, dtype=dtype)
    init_embed(b, cfg)
    period_specs = {}

    def one_period(key):
        pb = ParamBuilder(key, dtype=dtype)
        for pos in range(cfg.period):
            _init_block(pb.scope(f"pos{pos}"), cfg, pos, tp)
        period_specs.update(pb.specs)
        return pb.params

    # vmapped over the periods' keys: one batched op per leaf, so a jitted
    # init compiles in O(period), not O(num_layers)
    keys = jnp.stack([jax.random.fold_in(rng, 1000 + p)
                      for p in range(cfg.num_periods)])
    b.params["layers"] = jax.vmap(one_period)(keys)
    b.specs["layers"] = jax.tree.map(
        lambda s: (None,) + tuple(s), period_specs,
        is_leaf=lambda s: isinstance(s, tuple))
    return b.params, b.specs


def init_model_shapes(rng, cfg, tp: int = 1):
    """ShapeDtypeStruct tree of the params (no allocation) + spec tree."""
    closure = {}

    def f(r):
        p, s = init_model(r, cfg, tp)
        closure["specs"] = s
        return p

    shapes = jax.eval_shape(f, rng)
    return shapes, closure["specs"]


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg, inputs, constrain: Constrain):
    if cfg.frontend == "embed_stub":
        x = inputs.astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, cfg, inputs)
        x = constrain(x, ("batch", None, None))
    return x


def _block_forward(p, cfg, pos, x, positions, tp, impl, constrain,
                   collect_aux: bool, valid=None):
    """One block.  Returns (x, aux, route): route is the held experts'
    pair counts (None unless the layer holds a share of its experts);
    ``valid`` (B, S) marks the tokens a held-expert layer computes."""
    mixer = cfg.mixer_at(pos)
    h = rmsnorm(x, p["norm1"]["w"], cfg.norm_eps)
    # Megatron-SP boundary: gather S at block entry so the block computes
    # TP-sharded (d/heads over "model"); without this hint GSPMD keeps S
    # sharded and ALL-GATHERS THE WEIGHTS instead (full f32 dW replicas
    # on every chip — +35 GB at jamba scale, dry-run §Perf log).
    h = constrain(h, ("batch", None, None))
    aux = None
    if mixer == "attn":
        out, (k, v) = attn_mod.full_attention(
            p["mixer"], cfg, h, positions, tp, impl=impl,
            constrain=constrain, pos=pos)
        if collect_aux:
            aux = {"k": k, "v": v}
    elif mixer == "mamba":
        out, state = mamba_mod.mamba_forward(
            p["mixer"], cfg, h, return_state=True, impl=impl,
            constrain=constrain)
        if collect_aux:
            aux = state
    elif mixer == "mlstm":
        out, state = xlstm_mod.mlstm_forward(
            p["mixer"], cfg, h, return_state=True)
        if collect_aux:
            aux = state
    elif mixer == "slstm":
        out, state = xlstm_mod.slstm_forward(
            p["mixer"], cfg, h, return_state=True)
        if collect_aux:
            aux = state
    x = x + out
    route = None
    if cfg.ffn_at(pos) != "none":
        h = rmsnorm(x, p["norm2"]["w"], cfg.norm_eps)
        h = constrain(h, ("batch", None, None))   # SP gather (see above)
        if cfg.ffn_at(pos) == "dense":
            y = moe_mod.dense_ffn(p["ffn"], cfg, h, constrain=constrain)
        elif cfg.moe.held is not None:
            y, route = moe_mod.held_moe_ffn(p["ffn"], cfg, h, impl=impl,
                                            valid=valid)
        else:
            # collect_aux == the serving-prefill path -> inference
            # capacity policy (generation must not drop tokens)
            y = moe_mod.moe_ffn(p["ffn"], cfg, h, constrain=constrain,
                                inference=collect_aux)
        x = x + y
    # Megatron-style sequence parallelism: the inter-block residual is
    # sharded on S over the model axis ("seq" -> "model" under training
    # rules) so the per-period remat checkpoints are TP-sharded instead
    # of replicated — 16x smaller saved activations (see §Perf log).
    x = constrain(x, ("batch", "seq", None))
    return x, aux, route


def forward(params, cfg, inputs, positions, tp: int = 1, *,
            impl: str = "ref", return_aux: bool = False,
            constrain: Constrain = _IDENTITY, remat: bool = False,
            last_only: bool = False, lengths=None):
    """Full-sequence forward.  Returns logits (B,S,vocab_padded), or
    (logits, aux) with ``return_aux`` where aux is the per-period stacked
    tree of per-position KV / final state (the serving prefill products).
    ``last_only`` computes the LM head on the final position only (the
    serving prefill path — full 32K-position logits would be ~100s of GB);
    for rows right-padded to a bucket, ``lengths`` (B,) gives each row's
    valid tokens and the head reads position ``lengths - 1`` instead,
    and a held-expert layer computes only the valid tokens.
    """
    x = _embed_inputs(params, cfg, inputs, constrain)
    valid = None
    if lengths is not None:
        valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]

    # Per-LAYER remat (not per-period): inside a period's backward every
    # position's weight-gradient is live simultaneously; for jamba's
    # period of 8 that was ~30 GB/chip of f32 dW temporaries (dry-run
    # §Perf log).  Checkpointing each block bounds live dW to one layer.
    block = _block_forward
    if remat:
        block = jax.checkpoint(
            partial(_block_forward), prevent_cse=False,
            static_argnums=(1, 2, 5, 6, 7, 8))

    def period_body(x, layer_p):
        layer_p, x = grad_barrier((layer_p, x))
        auxes, routes = {}, {}
        for pos in range(cfg.period):
            x, aux, route = block(layer_p[f"pos{pos}"], cfg, pos, x,
                                  positions, tp, impl, constrain,
                                  return_aux, valid)
            if return_aux:
                auxes[f"pos{pos}"] = aux
            if route is not None:
                routes[f"pos{pos}"] = route
        if routes:
            auxes["route"] = routes
        return x, (auxes if return_aux else None)

    x, aux = jax.lax.scan(period_body, x, params["layers"])
    if last_only and lengths is not None:
        x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = lm_logits(params, cfg, x)
    logits = constrain(logits, ("batch", None, "model"))
    if return_aux:
        return logits, aux
    return logits


def lm_loss(params, cfg, tokens_or_embeds, labels, positions, tp: int = 1, *,
            impl: str = "ref", constrain: Constrain = _IDENTITY,
            remat: bool = True, ce_chunk: int = 512):
    """Next-token cross entropy; padded vocab columns masked out.

    The LM head + CE run CHUNKED over the sequence (checkpointed scan):
    full (B,S,V) f32 logits at qwen3/train_4k scale are ~0.6 GB/chip and
    the CE's exp/log temporaries multiply that several times (dry-run
    §Perf log); chunking caps it at (B,ce_chunk,V/​tp).
    """
    # run the trunk WITHOUT the LM head
    x = _embed_inputs(params, cfg, tokens_or_embeds, constrain)

    block = _block_forward
    if remat:
        block = jax.checkpoint(
            partial(_block_forward), prevent_cse=False,
            static_argnums=(1, 2, 5, 6, 7, 8))

    def period_body(x, layer_p):
        # barrier ties the sliced layer params to the loop-varying carry
        # so the CPU backend cannot hoist f32 upcasts of the WHOLE
        # stacked weights out of the scan (§Perf log; no-op on TPU)
        layer_p, x = grad_barrier((layer_p, x))
        for pos in range(cfg.period):
            x, _, _ = block(layer_p[f"pos{pos}"], cfg, pos, x, positions,
                            tp, impl, constrain, False)
        return x, None

    x, _ = jax.lax.scan(period_body, x, params["layers"])

    B, S, _ = x.shape
    c = min(ce_chunk, S)
    while S % c:
        c -= 1
    nc = S // c
    xc = x.reshape(B, nc, c, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, c).transpose(1, 0, 2)
    pad_mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
                if cfg.vocab_padded != cfg.vocab_size else None)

    @jax.checkpoint
    def ce_chunk_body(acc, args):
        xi, li = args
        logits = lm_logits(params, cfg, xi).astype(jnp.float32)
        logits = constrain(logits, ("batch", None, "model"))
        if pad_mask is not None:
            logits = jnp.where(pad_mask, NEG_INF, logits)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(ce_chunk_body, jnp.zeros((), jnp.float32),
                            (xc, lc))
    return total / (B * S)


# ---------------------------------------------------------------------------
# Caches + decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_seq: int, tp: int = 1,
               dtype=None):
    """Per-layer cache tree, leaves stacked over periods (leading dim P)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    per_pos = {}
    for pos in range(cfg.period):
        mixer = cfg.mixer_at(pos)
        if mixer == "attn":
            shape = attn_mod.cache_shape(cfg, batch, max_seq, tp, pos)
            per_pos[f"pos{pos}"] = {"k": jnp.zeros(shape, dtype),
                                    "v": jnp.zeros(shape, dtype)}
        elif mixer == "mamba":
            m = cfg.mamba
            per_pos[f"pos{pos}"] = {
                "conv": jnp.zeros((batch, m.d_conv - 1, cfg.d_inner), dtype),
                "ssm": jnp.zeros((batch, cfg.d_inner, m.d_state),
                                 jnp.float32),
            }
        elif mixer == "mlstm":
            per_pos[f"pos{pos}"] = xlstm_mod.mlstm_init_state(cfg, batch)
        elif mixer == "slstm":
            per_pos[f"pos{pos}"] = xlstm_mod.slstm_init_state(cfg, batch)
    P = cfg.num_periods
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (P,) + leaf.shape), per_pos)


def cache_specs(cfg, tp: int = 1):
    """Logical PartitionSpecs for the cache tree (mirrors init_cache)."""
    kv_spec = ("model" if cfg.kv_shard_mode(tp) == "heads" else None)
    per_pos = {}
    for pos in range(cfg.period):
        mixer = cfg.mixer_at(pos)
        if mixer == "attn":
            # "seq" resolves to the data axis for long-context decode
            # (context-parallel KV) and to None otherwise (sharding.py)
            s = (None, kv_spec, "batch", None, "seq")
            per_pos[f"pos{pos}"] = {"k": s, "v": s}
        elif mixer == "mamba":
            per_pos[f"pos{pos}"] = {
                "conv": (None, "batch", None, "model"),
                "ssm": (None, "batch", "model", None)}
        elif mixer == "mlstm":
            per_pos[f"pos{pos}"] = {"C": (None, "batch", None, None, None),
                                    "n": (None, "batch", None, None),
                                    "m": (None, "batch", None)}
        elif mixer == "slstm":
            per_pos[f"pos{pos}"] = {k: (None, "batch", None)
                                    for k in ("c", "n", "h", "m")}
    return per_pos


def write_prefill_to_cache(cfg, cache, aux, seq_len: int, lengths=None):
    """Populate a fresh cache tree from ``forward(return_aux=True)`` aux.

    attn: K/V written left-aligned (a window layer's ring holds the last
    window tokens at their rotated slots: of all ``seq_len``, or with
    ``lengths`` (B,) of each right-padded row's valid tokens);
    recurrent mixers: final state replaces the zero state.
    """
    out = {}
    for pos in range(cfg.period):
        key = f"pos{pos}"
        mixer = cfg.mixer_at(pos)
        if mixer == "attn":
            w = cfg.window_at(pos)
            out[key] = {n: _write_kv(cache[key][n], aux[key][n], w, lengths)
                        for n in ("k", "v")}
        else:
            out[key] = jax.tree.map(
                lambda c, s: s.astype(c.dtype).reshape(c.shape),
                cache[key], aux[key])
    return out


def _write_kv(cache, kv, window, lengths=None):
    """cache (P,H,B,D,Sc); kv (P,B,S,H,D)."""
    def one(c, x):
        ck, _ = attn_mod.prefill_into_cache(c, c, x, x, seq_lens=lengths,
                                            window=window)
        return ck
    return jax.vmap(one)(cache, kv)


def decode_forward(params, cfg, inputs, positions, cache, seq_lens,
                   tp: int = 1, *, impl: str = "ref",
                   constrain: Constrain = _IDENTITY,
                   return_routing: bool = False):
    """One-token decode.  inputs (B,1) tokens or (B,1,d) embeds;
    positions (B,1) or (B,1,3); seq_lens (B,) tokens already cached.
    Returns (logits (B,1,vocab_padded), new_cache), and with
    ``return_routing`` the held experts' pair counts per period and
    layer (``{"pos<i>": (P, num_held)}``, empty without a held share).
    """
    x = _embed_inputs(params, cfg, inputs, constrain)

    # The cache rides the scan CARRY (not xs/ys): a while-loop carry that
    # is dynamic-update-sliced in place aliases to a single buffer, where
    # an xs->ys cache would double-buffer ~5 GB/chip at decode_32k scale
    # (measured in the dry-run; see EXPERIMENTS.md §Dry-run notes).
    # Attention layers write and read their K/V in that buffer at the
    # layer's index; the small recurrent states are sliced out and back.
    def period_body(carry, scanned):
        x, cache = carry
        layer_p, idx = scanned
        layer_p, x = grad_barrier((layer_p, x))
        cache = dict(cache)
        routes = {}
        for pos in range(cfg.period):
            key = f"pos{pos}"
            p = layer_p[key]
            mixer = cfg.mixer_at(pos)
            h = rmsnorm(x, p["norm1"]["w"], cfg.norm_eps)
            if mixer == "attn":
                out, ck, cv = attn_mod.decode_attention(
                    p["mixer"], cfg, h, positions, cache[key]["k"],
                    cache[key]["v"], idx, seq_lens, tp, impl=impl, pos=pos)
                cache[key] = {"k": ck, "v": cv}
            else:
                step = {"mamba": mamba_mod.mamba_decode_step,
                        "mlstm": xlstm_mod.mlstm_decode_step,
                        "slstm": xlstm_mod.slstm_decode_step}[mixer]
                c = jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, idx, 0, keepdims=False), cache[key])
                out, st = step(p["mixer"], cfg, h, c)
                cache[key] = jax.tree.map(
                    lambda full, s: jax.lax.dynamic_update_index_in_dim(
                        full, s.astype(full.dtype), idx, 0), cache[key], st)
            x = x + out
            if cfg.ffn_at(pos) != "none":
                h = rmsnorm(x, p["norm2"]["w"], cfg.norm_eps)
                if cfg.ffn_at(pos) == "dense":
                    y = moe_mod.dense_ffn(p["ffn"], cfg, h,
                                          constrain=constrain)
                elif cfg.moe.held is not None:
                    y, routes[key] = moe_mod.held_moe_ffn(
                        p["ffn"], cfg, h, impl=impl)
                else:
                    y = moe_mod.moe_ffn(p["ffn"], cfg, h,
                                        constrain=constrain, dropless=True)
                x = x + y
        return (x, cache), routes

    P_ = cfg.num_periods
    (x, new_cache), routes = jax.lax.scan(
        period_body, (x, cache),
        (params["layers"], jnp.arange(P_, dtype=jnp.int32)))
    logits = lm_logits(params, cfg, x)
    if return_routing:
        return logits, new_cache, routes
    return logits, new_cache


def greedy_sample(logits, vocab_size: int):
    """Argmax over the unpadded vocab.  logits (B,1,Vp) -> (B,1) int32."""
    v = logits[..., :vocab_size]
    return jnp.argmax(v, axis=-1).astype(jnp.int32)
