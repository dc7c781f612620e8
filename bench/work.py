"""Work a step needs, from shapes and live lengths: FLOPs and bytes.

Counts are of the algorithm, not of an implementation: bucket padding,
free slots and dead pages are not counted, so a roofline share reads
the same work whatever computes it.  A multiply-add is two FLOPs.

``dims`` is ``bench.reference.Dims``; bfloat16 weights and KV (two
bytes an element) are what the configurations serve.
"""
from __future__ import annotations

from typing import Iterable

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
