"""Mixture-of-Experts FFN with grouped top-k dispatch (T5X/Mesh-style).

Tokens are processed in groups of ``group_size``; per group each expert has
capacity ``ceil(g * top_k / E * capacity_factor)``.  Dispatch/combine are
one-hot einsums — fully static shapes, shardable under GSPMD.

Expert partitioning:
  * ``ep`` (experts % tp == 0): expert dim sharded over the model axis;
    GSPMD materializes the token all-to-all at the dispatch einsum.
  * ``tp`` (else, e.g. Mixtral 8e over 16 chips): every expert's hidden dim
    sharded over the model axis (pure tensor parallelism).

A configuration that holds one chip's share of the experts
(``MoEConfig.held``) runs ``held_moe_ffn`` instead: the router spans
every expert, the (token, choice) pairs that fall on held experts are
computed without capacity or drops, and the absent experts add nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ParamBuilder, act_fn


def moe_partition(cfg, tp: int) -> str:
    """'ep': experts sharded over the data axis (GShard all-to-all
    dataflow) + hidden over model.  'tp': experts replicated over data
    with d FSDP-sharded (contracted dim -> cheap per-layer weight
    all-gather) + hidden over model — used when E doesn't divide the
    data axis (e.g. Mixtral's 8 experts on a 16-wide axis)."""
    m = cfg.moe
    if m.partition != "auto":
        return m.partition
    return "ep" if m.num_experts % tp == 0 else "tp"


def init_moe(b: ParamBuilder, cfg, tp: int):
    """Router over all ``num_experts``; expert banks of the held ones."""
    m = cfg.moe
    d, E, f = cfg.d_model, m.num_experts, m.d_ff_expert
    if moe_partition(cfg, tp) == "ep":
        w_spec_in = ("expert", None, "model")
        w_spec_out = ("expert", "model", None)
    else:
        w_spec_in = (None, "data", "model")
        w_spec_out = (None, "model", "data")
    b.param("router", (d, E), (None, None))
    Eh = m.num_held
    b.param("w_gate", (Eh, d, f), w_spec_in)
    b.param("w_in", (Eh, d, f), w_spec_in)
    b.param("w_out", (Eh, f, d), w_spec_out)


def held_moe_ffn(params, cfg, x, *, impl: str = "ref", valid=None):
    """This chip's share of an expert layer.  x (B, S, d); ``valid``
    (B, S) marks the tokens to compute (None: all).  Returns
    (y (B, S, d), tokens per held expert (num_held,) int32).

    Each token is routed over all ``num_experts`` (softmax, top-k, the
    k gates renormalised to sum to one); of its k (token, choice) pairs
    those on held experts are computed and weighted by their gates.
    With ``impl`` ``pallas`` / ``interpret`` the pairs are sorted by
    held expert and run through the grouped SwiGLU kernel
    (``kernels.moe_gmm``), which touches no weight of an expert that
    got no pair; ``ref`` computes every held expert on every token and
    weights the unrouted ones by zero (XLA-native, differentiable).
    """
    m = cfg.moe
    B, S, d = x.shape
    T, k, Eh = B * S, m.top_k, m.num_held
    held = tuple(range(m.num_experts)) if m.held is None else m.held
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt, params["router"],
                        preferred_element_type=jnp.float32)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gate = gate / gate.sum(-1, keepdims=True)                 # (T, k)
    # local index of each choice's expert; Eh for one held elsewhere
    local = np.full((m.num_experts,), Eh, np.int32)
    local[list(held)] = np.arange(Eh)
    e = jnp.asarray(local)[idx]
    if valid is not None:
        e = jnp.where(valid.reshape(T, 1), e, Eh)
    # pairs per group (one-hot sums: the TPU compiler refuses some
    # scatters inside the layer scan)
    counts = (e.reshape(-1, 1) == jnp.arange(Eh + 1)).sum(0, jnp.int32)
    if impl in ("pallas", "interpret"):
        from repro.kernels import moe_gmm
        flat = e.reshape(-1)
        order = jnp.argsort(flat, stable=True)      # by expert, others last
        h = moe_gmm.moe_gmm(xt[order // k], params["w_gate"],
                            params["w_in"], params["w_out"], counts,
                            act=act_fn(cfg.act),
                            interpret=impl == "interpret")
        # back to (token, choice) order; pairs held elsewhere are zero
        h = h[jnp.argsort(order)].reshape(T, k, d)
        y = jnp.einsum("tkd,tk->td", h, gate.astype(h.dtype),
                       preferred_element_type=jnp.float32)
    else:
        act = act_fn(cfg.act)
        w = jnp.einsum("tk,tke->te", gate,
                       (e[..., None] == jnp.arange(Eh)).astype(jnp.float32))
        h = act(jnp.einsum("td,edf->tef", xt, params["w_gate"])) * \
            jnp.einsum("td,edf->tef", xt, params["w_in"])
        y = jnp.einsum("tef,efd,te->td", h, params["w_out"],
                       w.astype(h.dtype), preferred_element_type=jnp.float32)
    return y.reshape(B, S, d).astype(x.dtype), counts[:Eh]


def moe_ffn(params, cfg, x, *, group_size: int = 256, constrain=None,
            dropless: bool = False, inference: bool = False):
    """x (B, S, d) -> (B, S, d).

    Capacity policy (a dropped token corrupts *generation*, but is a
    mild regularizer in *training* — Switch):
      * dropless=True   — C = group size, exact; used for decode and any
        small-group path (cheap there).
      * inference=True  — serving prefill: capacity factor boosted to
        >= 2.0 (P(drop) is ~4-sigma-rare at group>=256) and exact
        dropless for small groups.  Exact sort-based dropless dispatch
        is future kernel work (DESIGN.md §8).
      * default         — training: cfg capacity_factor (1.25).
    """
    m = cfg.moe
    act = act_fn(cfg.act)
    constrain = constrain or (lambda a, spec: a)
    B, S, d = x.shape
    T = B * S
    g = min(group_size, T)
    while T % g:
        g -= 1
    N = T // g
    E, k = m.num_experts, m.top_k
    if inference and g <= 64:
        dropless = True
    if dropless:
        C = g
    else:
        cf = max(m.capacity_factor, 2.0) if inference else m.capacity_factor
        C = max(1, math.ceil(g * k / E * cf))

    xg = x.reshape(N, g, d)
    logits = jnp.einsum("ngd,de->nge", xg, params["router"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, k)          # (N,g,k)
    gate_k = gate_k / jnp.clip(gate_k.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity buffer
    onehot_e = jax.nn.one_hot(idx_k, E, dtype=jnp.float32)   # (N,g,k,E)
    flat = onehot_e.reshape(N, g * k, E)
    pos = jnp.cumsum(flat, axis=1) - 1.0                      # (N,g*k,E)
    pos = pos.reshape(N, g, k, E)
    in_cap = (pos < C) & (onehot_e > 0)
    pos_cap = jnp.clip(pos, 0, C - 1).astype(jnp.int32)
    onehot_c = jax.nn.one_hot(pos_cap, C, dtype=jnp.float32)  # (N,g,k,E,C)
    combine = jnp.einsum("ngk,ngke,ngkec->ngec",
                         gate_k.astype(jnp.float32),
                         (onehot_e * in_cap).astype(jnp.float32), onehot_c)
    dispatch = (combine > 0).astype(x.dtype)                  # (N,g,E,C)

    ep = (m.num_experts % 16 == 0 if m.partition == "auto"
          else m.partition == "ep")
    if ep:
        # expert-space layout: e sharded ("expert" -> data axis), token
        # d sharded over model (the capacity buffers stay 1/(ep*tp)
        # sized), n replicated — the n@data -> e@data reshard IS the
        # GShard dispatch all-to-all.
        in_spec = (None, "expert", None, "model")
        h_spec = (None, "expert", None, "model")
    else:
        # 'tp' layout: experts replicated, tokens stay data-sharded,
        # hidden on model; expert weights FSDP-gathered per layer.
        in_spec = ("batch", None, None, None)
        h_spec = ("batch", None, None, "model")
    # dispatch: compute locally in token space (n@data), THEN reshard to
    # expert space (e@data) — the back-to-back constraints force GSPMD to
    # lower the reshard as an all-to-all moving 1/|data| of the tokens;
    # constraining only the einsum output lets it all-gather ALL tokens
    # to every chip in f32 instead (2 GB/chip at jamba scale, §Perf log).
    expert_in = jnp.einsum("ngec,ngd->necd", dispatch, xg)
    expert_in = constrain(expert_in, ("batch", None, None, None))
    expert_in = constrain(expert_in, in_spec)
    h = act(jnp.einsum("necd,edf->necf", expert_in, params["w_gate"])) * \
        jnp.einsum("necd,edf->necf", expert_in, params["w_in"])
    h = constrain(h, h_spec)
    out = jnp.einsum("necf,efd->necd", h, params["w_out"])
    out = constrain(out, in_spec)
    out = constrain(out, ("batch", None, None, None))  # combine: back to
    y = jnp.einsum("ngec,necd->ngd", combine.astype(x.dtype), out)
    y = constrain(y, ("batch", None, None))            # token space
    return y.reshape(B, S, d)


def init_dense_ffn(b: ParamBuilder, cfg):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.ffn_glu:
        b.param("w_gate", (d, f), (None, "model"))
    b.param("w_in", (d, f), (None, "model"))
    b.param("w_out", (f, d), ("model", None))


def dense_ffn(params, cfg, x, constrain=None):
    act = act_fn(cfg.act)
    constrain = constrain or (lambda a, spec: a)
    if cfg.ffn_glu:
        h = act(jnp.einsum("bsd,df->bsf", x, params["w_gate"])) * \
            jnp.einsum("bsd,df->bsf", x, params["w_in"])
    else:
        h = act(jnp.einsum("bsd,df->bsf", x, params["w_in"]))
    # pin the hidden to TP-sharded: left to itself GSPMD sometimes picks
    # full-f activations + replicated dW (dry-run §Perf log)
    h = constrain(h, ("batch", None, "model"))
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"])
