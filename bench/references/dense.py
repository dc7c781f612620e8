"""The dense decoder's plain float32 reference, its fp8 control, and
the work counts of its steps and kernels.

Written from the configuration file alone (``configs/*.json``): it
imports nothing of the program and takes none of its arrays.  The
weights come from ``weights.py`` with the run's seed, one layer at a
time, at the values the served model holds (bfloat16, widened).

The block is the one the configuration file states: pre-norm RMSNorm,
rotary attention (half-split rotation) with grouped KV heads and
optional q/k/v biases, then an MLP (tanh-GELU, or SiLU-gated), a final
RMSNorm and the LM head (tied to the embedding where the file says so).
Full causal attention in every layer, a dense MLP in every layer.
Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise computed in bfloat16 passes.

``quant=True`` is the control: the same forward with every weight
matrix and every matrix input rounded to float8 (e4m3, per output
channel and per token, with float32 accumulation), the lower precision
a later change to bfloat16 serving would be tempted by.

Work counts are of the algorithm, not of an implementation: bucket
padding, free slots and dead pages are not counted, so a roofline share
reads the same work whatever computes it.  A multiply-add is two FLOPs;
bfloat16 weights and KV (two bytes an element) are what the
configurations serve.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    layers: int
    glu: bool
    act: str
    qkv_bias: bool
    tied: bool
    eps: float
    theta: float
    dtype: str

    @property
    def vocab_padded(self) -> int:
        return int(math.ceil(self.vocab / 256) * 256)


def dims_of(model: dict) -> Dims:
    """The reference's shape from a configuration file's ``model`` and
    ``block`` entries."""
    m, b = model["model"], model["block"]
    return Dims(d=m["hidden_size"], heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=b["head_dim"],
                ffn=m["intermediate_size"], vocab=m["vocab_size"],
                layers=m["num_hidden_layers"], glu=b["mlp"] == "silu_glu",
                act=b["mlp"], qkv_bias=b["qkv_bias"],
                tied=m["tie_word_embeddings"], eps=b["norm_eps"],
                theta=float(m["rope_theta"]), dtype=b["dtype"])


def layout(dims: Dims) -> Dict[str, tuple]:
    """Path -> per-layer shape of every weight, in the served layout."""
    d, D = dims.d, dims.head_dim
    out = {"embed/tok": (dims.vocab_padded, d), "final_norm/w": (d,)}
    if not dims.tied:
        out["lm_head/w"] = (d, dims.vocab_padded)
    lay = {"norm1/w": (d,), "mixer/wq": (d, dims.heads * D),
           "mixer/wk": (d, dims.kv_heads * D),
           "mixer/wv": (d, dims.kv_heads * D),
           "mixer/wo": (dims.heads * D, d), "norm2/w": (d,),
           "ffn/w_in": (d, dims.ffn), "ffn/w_out": (dims.ffn, d)}
    if dims.qkv_bias:
        lay.update({"mixer/bq": (dims.heads * D,),
                    "mixer/bk": (dims.kv_heads * D,),
                    "mixer/bv": (dims.kv_heads * D,)})
    if dims.glu:
        lay["ffn/w_gate"] = (d, dims.ffn)
    for k, v in lay.items():
        out[f"layers/pos0/{k}"] = v
    return out


def check_registry(cfg, dims: Dims) -> None:
    """The program's registry entry must be the configuration file's
    dense block: the same widths and switches, and no sliding window."""
    have = {"d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "num_layers": cfg.num_layers, "ffn_glu": cfg.ffn_glu,
            "qkv_bias": cfg.qkv_bias, "tie": cfg.tie_embeddings,
            "norm_eps": cfg.norm_eps, "rope_theta": float(cfg.rope_theta),
            "dtype": cfg.dtype, "act": cfg.act}
    want = {"d_model": dims.d, "num_heads": dims.heads,
            "num_kv_heads": dims.kv_heads, "head_dim": dims.head_dim,
            "d_ff": dims.ffn, "vocab_size": dims.vocab,
            "num_layers": dims.layers, "ffn_glu": dims.glu,
            "qkv_bias": dims.qkv_bias, "tie": dims.tied,
            "norm_eps": dims.eps, "rope_theta": dims.theta,
            "dtype": dims.dtype,
            "act": "silu" if dims.glu else "gelu"}
    diff = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if diff or cfg.sliding_window:
        raise ValueError(f"{cfg.name}: the program's config differs from "
                         f"the configuration file: {diff}")


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1, half-split rotation."""
    S, _, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, w, *, dims: Dims, quant: bool):
    """One block over x (B, S, d); causal over each row."""
    p = "layers/pos0/"
    h = _rms(x, w[p + "norm1/w"], dims.eps)
    q = _mm(h, w[p + "mixer/wq"], quant)
    k = _mm(h, w[p + "mixer/wk"], quant)
    v = _mm(h, w[p + "mixer/wv"], quant)
    if dims.qkv_bias:
        q, k, v = q + w[p + "mixer/bq"], k + w[p + "mixer/bk"], \
            v + w[p + "mixer/bv"]
    D, G = dims.head_dim, dims.heads // dims.kv_heads

    def attend(qkv):
        q1, k1, v1 = qkv
        S = q1.shape[0]
        q1 = _rope(q1.reshape(S, dims.heads, D), dims.theta)
        k1 = _rope(k1.reshape(S, dims.kv_heads, D), dims.theta)
        v1 = v1.reshape(S, dims.kv_heads, D)
        qg = q1.reshape(S, dims.kv_heads, G, D)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k1, precision=HI) / math.sqrt(D)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v1,
                       precision=HI)
        return o.reshape(S, dims.heads * D)

    att = jax.lax.map(attend, (q, k, v))
    x = x + _mm(att, w[p + "mixer/wo"], quant)
    h = _rms(x, w[p + "norm2/w"], dims.eps)
    if dims.glu:
        u = jax.nn.silu(_mm(h, w[p + "ffn/w_gate"], quant)) * \
            _mm(h, w[p + "ffn/w_in"], quant)
    else:
        u = jax.nn.gelu(_mm(h, w[p + "ffn/w_in"], quant), approximate=True)
    return x + _mm(u, w[p + "ffn/w_out"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(h, norm_w, head_w, tok, alt, *, eps, vocab, quant):
    """Logits of one row (S, d) -> per position: the best logit, the
    logit of ``tok``, the logit of ``alt`` and the argmax."""
    lg = _mm(_rms(h, norm_w, eps), head_w, quant)[:, :vocab]
    pick = lambda i: jnp.take_along_axis(lg, i[:, None], 1)[:, 0]
    return lg.max(-1), pick(tok), pick(alt), jnp.argmax(lg, -1)


class Reading(NamedTuple):
    best: np.ndarray        # (B, S) the reference's best logit
    at_next: np.ndarray     # (B, S) its logit of the served next token
    at_alt: np.ndarray      # (B, S) its logit of ``alt``'s token
    argmax: np.ndarray      # (B, S) this forward's own first choice


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
    lay = {k: (v, served) for k, v in shapes.items()
           if k.startswith("layers/")}
    emb = W.leaf("embed/tok", shapes["embed/tok"], served, seed, dims.tied,
                 device)
    x = jnp.take(emb, put(tokens), axis=0)
    for i in range(dims.layers):
        x = _layer(x, W.layer_params(lay, seed, i, device),
                   dims=dims, quant=quant)
    norm = W.leaf("final_norm/w", shapes["final_norm/w"], served, seed,
                  dims.tied, device)
    head = emb.T if dims.tied else W.leaf(
        "lm_head/w", shapes["lm_head/w"], served, seed, dims.tied, device)
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


def layer_matmul_params(dims) -> int:
    """Weights of one block's matrix products (biases and norms apart)."""
    d, D = dims.d, dims.head_dim
    attn = d * dims.heads * D * 2 + d * dims.kv_heads * D * 2
    ffn = d * dims.ffn * (3 if dims.glu else 2)
    return attn + ffn


def layer_vector_params(dims) -> int:
    v = 2 * dims.d
    if dims.qkv_bias:
        v += (dims.heads + 2 * dims.kv_heads) * dims.head_dim
    return v


def weight_params(dims) -> int:
    """Every weight the served model holds (padded vocabulary rows too)."""
    emb = dims.vocab_padded * dims.d
    head = 0 if dims.tied else dims.d * dims.vocab_padded
    return dims.layers * (layer_matmul_params(dims) + layer_vector_params(
        dims)) + emb + head + dims.d


def weight_bytes(dims) -> int:
    return weight_params(dims) * BYTES


def kv_bytes_per_token(dims) -> int:
    """K and V of one token in every layer."""
    return 2 * dims.layers * dims.kv_heads * dims.head_dim * BYTES


def head_flops(dims) -> int:
    """LM head over the real vocabulary, one token."""
    return 2 * dims.d * dims.vocab


def attention_flops(dims, q_positions: Iterable[int]) -> int:
    """QK^T and PV of one query at each attended length, all layers."""
    per = 4 * dims.heads * dims.head_dim * dims.layers
    return per * sum(q_positions)


def prefill_attention_flops(dims, n: int) -> int:
    """Causal attention over ``n`` valid prompt tokens: query i attends
    i + 1 keys."""
    return 4 * dims.heads * dims.head_dim * dims.layers * n * (n + 1) // 2


def prefill_flops(dims, n: int) -> int:
    """One prompt of ``n`` valid tokens; the head runs on the last one."""
    return (2 * layer_matmul_params(dims) * dims.layers * n
            + prefill_attention_flops(dims, n) + head_flops(dims))


def decode_flops(dims, lens: Iterable[int]) -> int:
    """One token for each live slot; ``lens`` are the lengths each new
    token attends (cache plus itself)."""
    lens = list(lens)
    return (len(lens) * (2 * layer_matmul_params(dims) * dims.layers
                         + head_flops(dims))
            + attention_flops(dims, lens))


def paged_attention_bytes(dims, lens: Iterable[int]) -> int:
    """Live KV read by one decode step's attention, plus q and out."""
    lens = list(lens)
    kv = sum(lens) * kv_bytes_per_token(dims)
    q_out = 2 * len(lens) * dims.heads * dims.head_dim * dims.layers * BYTES
    return kv + q_out


def flash_prefill_bytes(dims, n: int) -> int:
    """q, k, v in and out once, all layers."""
    return (2 * dims.heads + 2 * dims.kv_heads) * dims.head_dim * n \
        * dims.layers * BYTES


def decode_bound_s(dims, slots: int, seq_len: int, peak_bw: float) -> float:
    """Least time of a decode step that reads every weight and a dense
    slot cache of ``slots`` x ``seq_len`` tokens."""
    return (weight_bytes(dims) + slots * seq_len * kv_bytes_per_token(dims)) \
        / peak_bw
