"""Mellum2-12B-A2.5B, one chip's share: the plain float32 reference, its
fp8 control, and the work counts of its steps and kernels.

Written from the configuration file alone (``configs/mellum2-*.json``,
whose top level holds the published ``config.json``): it imports nothing
of the program and takes none of its arrays.  The weights come from
``weights.py`` with the run's seed, one layer at a time, at the values
the served model holds (bfloat16, widened).

The block: pre-norm RMSNorm; grouped-query attention (no bias, no
QK-norm, no sinks) whose kind follows ``layer_types``: a
``sliding_attention`` layer keeps key j for query i when
``0 <= i - j < sliding_window`` and rotates with the default RoPE of
``rope_parameters["sliding_attention"]``; a ``full_attention`` layer is
causal and rotates with YaRN (``rope_parameters["full_attention"]``):
inverse frequencies blended between theta**(-2i/D) and the same divided
by ``factor`` by a linear ramp between the correction dims of
``beta_fast`` and ``beta_slow`` rotations over
``original_max_position_embeddings``, cos and sin scaled by
``attention_factor`` (half-split rotation).  Then the expert FFN: a
softmax router over all ``published.num_experts`` experts, the top
``num_experts_per_tok``, their gates renormalised to sum to one
(``norm_topk_prob``); of those pairs, the ones on the experts this chip
holds (``deployment.experts_held``) go through that expert's SwiGLU
(``moe_intermediate_size``), weighted by their gate.  What the other
chips' experts would add is left out, as the program leaves it out.
Final RMSNorm, untied head.

Full attention runs in blocks of queries over every key, window
attention in blocks of queries over the window's keys before them, and
each held expert over at most as many tokens as the most loaded one
got (read back per layer), so that eight rows of 8192 tokens fit one
chip.  Every matrix product is at ``Precision.HIGHEST``.

``quant=True`` is the control: every weight matrix and every matrix
input of a weight product (router and experts too) rounded to float8
e4m3 per output channel and per token, float32 accumulation.

Work counts are of the algorithm: a window layer attends
``min(length, window)`` keys, and a token's expert work is that of its
expected pairs on the held experts, ``top_k * held / num_experts``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.references.dense import Reading, _q8

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # queries per attention block
H_BLOCK = 1024          # positions per block of the head


class Dims(NamedTuple):
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layers: int
    kinds: tuple           # "window" | "full", per layer
    window: int
    theta: float
    yarn: tuple            # (factor, original max, beta_fast, beta_slow, scale)
    experts: int           # the router's outputs
    held: tuple            # expert ids this chip holds
    top_k: int
    expert_ffn: int
    eps: float
    tied: bool
    dtype: str

    @property
    def vocab_padded(self) -> int:
        return int(math.ceil(self.vocab / 256) * 256)

    @property
    def period(self) -> int:
        for p in range(1, self.layers + 1):
            if self.layers % p == 0 and all(
                    self.kinds[i] == self.kinds[i % p]
                    for i in range(self.layers)):
                return p
        return self.layers


KIND = {"sliding_attention": "window", "full_attention": "full"}


def dims_of(model: dict) -> Dims:
    """The reference's shape from the configuration file."""
    rope = model["rope_parameters"]
    full, win = rope["full_attention"], rope["sliding_attention"]
    if win["rope_theta"] != full["rope_theta"] or \
            win["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("this reference computes default RoPE on window "
                         "layers and YaRN at the same theta on full ones")
    held = tuple(model["deployment"]["experts_held"])
    if len(held) != model["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    return Dims(
        d=model["hidden_size"], heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        vocab=model["vocab_size"], layers=model["num_hidden_layers"],
        kinds=tuple(KIND[k] for k in model["layer_types"]),
        window=model["sliding_window"], theta=float(full["rope_theta"]),
        yarn=(float(full["factor"]),
              int(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"]),
              float(full["attention_factor"])),
        experts=model["published"]["num_experts"], held=held,
        top_k=model["num_experts_per_tok"],
        expert_ffn=model["moe_intermediate_size"],
        eps=model["rms_norm_eps"], tied=model["tie_word_embeddings"],
        dtype=model["block"]["dtype"])


def layout(dims: Dims) -> Dict[str, tuple]:
    """Path -> per-period shape of every weight, in the served layout."""
    d, D, f, Eh = dims.d, dims.head_dim, dims.expert_ffn, len(dims.held)
    out = {"embed/tok": (dims.vocab_padded, d), "final_norm/w": (d,),
           "lm_head/w": (d, dims.vocab_padded)}
    lay = {"norm1/w": (d,), "mixer/wq": (d, dims.heads * D),
           "mixer/wk": (d, dims.kv_heads * D),
           "mixer/wv": (d, dims.kv_heads * D),
           "mixer/wo": (dims.heads * D, d), "norm2/w": (d,),
           "ffn/router": (d, dims.experts), "ffn/w_gate": (Eh, d, f),
           "ffn/w_in": (Eh, d, f), "ffn/w_out": (Eh, f, d)}
    for pos in range(dims.period):
        for k, v in lay.items():
            out[f"layers/pos{pos}/{k}"] = v
    return out


def weight_law(path: str, shape, tied: bool):
    """The router (one column per expert) and the expert banks: each at
    1/sqrt of its fan-in, as the dense matrices."""
    name = path.rsplit("/", 1)[-1]
    if name == "router":
        return 0.0, 1.0 / math.sqrt(shape[0])
    if "/ffn/" in path and len(shape) == 3:
        return 0.0, 1.0 / math.sqrt(shape[1])
    return None


def check_registry(cfg, dims: Dims) -> None:
    """The program's registry entry must be this block and share."""
    m, y = cfg.moe, cfg.yarn
    have = {"d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "vocab_size": cfg.vocab_size, "num_layers": cfg.num_layers,
            "windows": tuple(cfg.window_at(i) for i in range(cfg.num_layers)),
            "rope_theta": float(cfg.rope_theta),
            "yarn": y and (y.factor, y.original_max_position, y.beta_fast,
                           y.beta_slow, y.attention_factor),
            "mixers": {cfg.mixer_at(i) for i in range(cfg.num_layers)},
            "ffns": {cfg.ffn_at(i) for i in range(cfg.num_layers)},
            "experts": m and m.num_experts, "top_k": m and m.top_k,
            "expert_ffn": m and m.d_ff_expert, "held": m and m.held,
            "glu": (cfg.ffn_glu, cfg.act), "qkv_bias": cfg.qkv_bias,
            "norm_eps": cfg.norm_eps, "tie": cfg.tie_embeddings,
            "dtype": cfg.dtype, "frontend": cfg.frontend,
            "rope_type": cfg.rope_type}
    want = {"d_model": dims.d, "num_heads": dims.heads,
            "num_kv_heads": dims.kv_heads, "head_dim": dims.head_dim,
            "vocab_size": dims.vocab, "num_layers": dims.layers,
            "windows": tuple(dims.window if k == "window" else None
                             for k in dims.kinds),
            "rope_theta": dims.theta, "yarn": dims.yarn,
            "mixers": {"attn"}, "ffns": {"moe"}, "experts": dims.experts,
            "top_k": dims.top_k, "expert_ffn": dims.expert_ffn,
            "held": dims.held, "glu": (True, "silu"), "qkv_bias": False,
            "norm_eps": dims.eps, "tie": dims.tied, "dtype": dims.dtype,
            "frontend": "token", "rope_type": "rope"}
    diff = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if diff:
        raise ValueError(f"{cfg.name}: the program's config differs from "
                         f"the configuration file: {diff}")


# -- the forward ---------------------------------------------------------------

def _mm(x, w, quant):
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(dims: Dims):
    """YaRN's inverse frequencies (D/2,) and its cos/sin scale."""
    factor, orig, fast, slow, scale = dims.yarn
    D, theta = dims.head_dim, dims.theta

    def corr(rot):
        return D * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), D - 1)
    if low == high:
        high += 0.001
    base = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp), scale


def _rope(x, inv, scale):
    """x (S, H, D) at positions 0..S-1, half-split rotation."""
    S, _, D = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    c, s = (jnp.cos(ang) * scale)[:, None], (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend(q, k, v, dims: Dims, window: Optional[int]):
    """One row: q (S, H, D), k/v (S, Hkv, D) -> (S, H * D), in blocks of
    queries: over every key (causal) or over the window's keys."""
    S, H, D = q.shape
    G = H // dims.kv_heads
    bq = min(Q_BLOCK, S)
    nb = -(-S // bq)
    pad = nb * bq - S
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    span = S if window is None else window + bq
    front = 0 if window is None else window
    k = jnp.pad(k, ((front, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((front, pad), (0, 0), (0, 0)))

    def block(b):
        q0 = b * bq
        qb = jax.lax.dynamic_slice_in_dim(q, q0, bq).reshape(
            bq, dims.kv_heads, G, D)
        k0 = 0 if window is None else q0       # key slab starts at q0 - W
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span)
        s = jnp.einsum("qhgd,khd->hgqk", qb, kb, precision=HI) / math.sqrt(D)
        qpos = q0 + jnp.arange(bq)[:, None]
        kpos = k0 - front + jnp.arange(span)[None, :]
        keep = (kpos <= qpos) & (kpos >= 0)
        if window is not None:
            keep &= qpos - kpos < window
        s = jnp.where(keep, s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), vb,
                       precision=HI)
        return o.reshape(bq, H * D)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(nb * bq, H * D)[:S]


@functools.partial(jax.jit, static_argnames=("dims", "kind", "quant"))
def _attention(x, w, *, dims: Dims, kind: str, quant: bool):
    """x + attention(rmsnorm(x)) over rows x (B, S, d); ``w`` the
    layer's leaves by name."""
    h = _rms(x, w["norm1/w"], dims.eps)
    q, k, v = (_mm(h, w[f"mixer/w{n}"], quant) for n in "qkv")
    D = dims.head_dim
    if kind == "window":
        inv = 1.0 / dims.theta ** (jnp.arange(0, D, 2, dtype=jnp.float32)
                                   / D)
        scale = 1.0
    else:
        inv, scale = yarn_inv_freq(dims)
    window = dims.window if kind == "window" else None

    def row(qkv):
        q1, k1, v1 = qkv
        S = q1.shape[0]
        return _attend(_rope(q1.reshape(S, dims.heads, D), inv, scale),
                       _rope(k1.reshape(S, dims.kv_heads, D), inv, scale),
                       v1.reshape(S, dims.kv_heads, D), dims, window)

    att = jax.lax.map(row, (q, k, v))
    return x + _mm(att, w["mixer/wo"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _route(x, w, *, dims: Dims, quant: bool):
    """The token's weight on each held expert (T, Eh): its renormalised
    gate where the expert is among its top k, else 0; and the normed
    input (T, d)."""
    h = _rms(x, w["norm2/w"], dims.eps).reshape(-1, dims.d)
    probs = jax.nn.softmax(_mm(h, w["ffn/router"], quant), -1)
    gate, idx = jax.lax.top_k(probs, dims.top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    held = jnp.asarray(dims.held)
    mine = idx[:, :, None] == held[None, None, :]          # (T, k, Eh)
    return jnp.einsum("tk,tke->te", gate, mine.astype(jnp.float32)), h


@functools.partial(jax.jit, static_argnames=("quant", "cap"))
def _experts(x, h, on, w, *, quant: bool, cap: int):
    """x + sum over held experts of their SwiGLU on the tokens routed to
    them, each weighted by its gate.  Each expert takes the ``cap``
    tokens with the largest weight on it, which holds every routed one
    when ``cap`` is at least its count."""
    B, S, d = x.shape

    def one(acc, e):
        wt, sel = jax.lax.top_k(on[:, e], cap)
        xe = h[sel]
        u = jax.nn.silu(_mm(xe, w["ffn/w_gate"][e], quant)) * \
            _mm(xe, w["ffn/w_in"][e], quant)
        ye = _mm(u, w["ffn/w_out"][e], quant) * wt[:, None]
        return acc.at[sel].add(ye), None

    acc, _ = jax.lax.scan(one, jnp.zeros((B * S, d), jnp.float32),
                          jnp.arange(on.shape[1]))
    return x + acc.reshape(B, S, d)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(h, norm_w, head_w, tok, alt, *, eps, vocab, quant):
    """One row (S, d) in blocks of positions -> per position: the best
    logit, the logit of ``tok``, of ``alt`` and the argmax."""
    S = h.shape[0]
    nb = -(-S // H_BLOCK)
    pad = nb * H_BLOCK - S
    h, tok, alt = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                   for a in (h, tok, alt))

    def block(b):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, b * H_BLOCK, H_BLOCK)
        lg = _mm(_rms(sl(h), norm_w, eps), head_w, quant)[:, :vocab]
        pick = lambda i: jnp.take_along_axis(lg, sl(i)[:, None], 1)[:, 0]
        return lg.max(-1), pick(tok), pick(alt), jnp.argmax(lg, -1)

    outs = jax.lax.map(block, jnp.arange(nb))
    return tuple(o.reshape(-1)[:S] for o in outs)


def _capacity(on, step: int = 256) -> int:
    """Tokens the most loaded held expert got, rounded up to ``step``
    (few distinct sizes to compile), at most every token."""
    most = int(np.asarray((on > 0).sum(0)).max())
    return int(min(on.shape[0], max(step, -(-most // step) * step)))


def forward(model: dict, seed: int, tokens: np.ndarray,
            alt: Optional[np.ndarray] = None, quant: bool = False,
            device=None) -> Reading:
    """Teacher-forced forward over right-padded rows ``tokens`` (B, S).
    ``next`` at position p is ``tokens[:, p + 1]``; ``alt`` (B, S) names
    another token per position whose logit is read too."""
    dims = dims_of(model)
    shapes = layout(dims)
    served = jnp.dtype(dims.dtype)
    put = lambda a: jax.device_put(a, device)
    emb = W.leaf("embed/tok", shapes["embed/tok"], served, seed, False,
                 device)
    x = jnp.take(emb, put(tokens), axis=0)
    del emb
    for i in range(dims.layers):
        pos = i % dims.period
        lay = {k: (v, served) for k, v in shapes.items()
               if k.startswith(f"layers/pos{pos}/")}
        got = W.layer_params(lay, seed, i // dims.period, device,
                             extra=weight_law)
        w = {k.split("/", 2)[2]: v for k, v in got.items()}
        x = _attention(x, w, dims=dims, kind=dims.kinds[i], quant=quant)
        on, h = _route(x, w, dims=dims, quant=quant)
        x = _experts(x, h, on, w, quant=quant, cap=_capacity(on))
    norm = W.leaf("final_norm/w", shapes["final_norm/w"], served, seed,
                  False, device)
    head = W.leaf("lm_head/w", shapes["lm_head/w"], served, seed, False,
                  device)
    nxt = np.zeros_like(tokens)
    nxt[:, :-1] = tokens[:, 1:]
    alt = nxt if alt is None else alt
    outs = [_head(x[b], norm, head, put(nxt[b]), put(alt[b]),
                  eps=dims.eps, vocab=dims.vocab, quant=quant)
            for b in range(tokens.shape[0])]
    return Reading(*(np.stack([np.asarray(o[i]) for o in outs])
                     for i in range(4)))


# -- work counts --------------------------------------------------------------

BYTES = 2


def attn_params(dims) -> int:
    d, D = dims.d, dims.head_dim
    return d * dims.heads * D * 2 + d * dims.kv_heads * D * 2


def expert_params(dims) -> int:
    """One expert's SwiGLU (gate, in, out)."""
    return 3 * dims.d * dims.expert_ffn


def held_pairs_per_token(dims) -> float:
    """Expected pairs a token puts on the held experts, a layer."""
    return dims.top_k * len(dims.held) / dims.experts


def layer_matmul_params(dims) -> int:
    """A layer's weights of matrix products: attention, router, the
    held experts."""
    return attn_params(dims) + dims.d * dims.experts + \
        len(dims.held) * expert_params(dims)


def weight_params(dims) -> int:
    """Every weight the served model holds."""
    per = layer_matmul_params(dims) + 2 * dims.d
    return dims.layers * per + 2 * dims.vocab_padded * dims.d + dims.d


def weight_bytes(dims) -> int:
    return weight_params(dims) * BYTES


def kv_bytes_per_token_layer(dims) -> int:
    return 2 * dims.kv_heads * dims.head_dim * BYTES


def attended(dims, length: int) -> int:
    """Keys one query at ``length`` attends, summed over the layers."""
    return sum(min(length, dims.window) if k == "window" else length
               for k in dims.kinds)


def token_matmul_flops(dims) -> float:
    """A token's weight-product FLOPs through every layer: attention and
    router, and its expected pairs on the held experts."""
    return 2 * dims.layers * (attn_params(dims) + dims.d * dims.experts
                              + held_pairs_per_token(dims)
                              * expert_params(dims))


def head_flops(dims) -> int:
    return 2 * dims.d * dims.vocab


def attention_flops(dims, q_positions: Iterable[int]) -> int:
    """QK^T and PV of one query at each attended length, all layers
    (window layers at most the window)."""
    per = 4 * dims.heads * dims.head_dim
    return per * sum(attended(dims, n) for n in q_positions)


def prefill_attention_flops(dims, n: int) -> int:
    """Query i of ``n`` attends i + 1 keys, at most the window."""
    per = 4 * dims.heads * dims.head_dim
    w = min(n, dims.window)
    window = w * (w + 1) // 2 + (n - w) * dims.window
    return per * sum(window if k == "window" else n * (n + 1) // 2
                     for k in dims.kinds)


def prefill_flops(dims, n: int) -> float:
    return token_matmul_flops(dims) * n + \
        prefill_attention_flops(dims, n) + head_flops(dims)


def decode_flops(dims, lens: Iterable[int]) -> float:
    lens = list(lens)
    return len(lens) * (token_matmul_flops(dims) + head_flops(dims)) + \
        attention_flops(dims, lens)


def paged_attention_bytes(dims, lens: Iterable[int]) -> int:
    """Live KV one decode step's attention reads (window layers their
    ring's valid part), plus q and out."""
    lens = list(lens)
    kv = sum(attended(dims, n) for n in lens) * kv_bytes_per_token_layer(dims)
    q_out = 2 * len(lens) * dims.heads * dims.head_dim * dims.layers * BYTES
    return kv + q_out


def flash_prefill_bytes(dims, n: int) -> int:
    """q, k, v in and out once, all layers."""
    return (2 * dims.heads + 2 * dims.kv_heads) * dims.head_dim * n \
        * dims.layers * BYTES


def slot_kv_bytes(dims, seq_len: int) -> int:
    """One slot of ``seq_len`` tokens: window layers keep a ring."""
    return attended(dims, seq_len) * kv_bytes_per_token_layer(dims)


def decode_bound_s(dims, slots: int, seq_len: int, peak_bw: float) -> float:
    """Least time of a decode step that reads every weight and every
    slot's cache in full."""
    return (weight_bytes(dims) + slots * slot_kv_bytes(dims, seq_len)) \
        / peak_bw
