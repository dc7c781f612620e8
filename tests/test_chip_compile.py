"""Compile-only checks of the Pallas kernels at real widths for TPU v5e.

Interpret mode runs any block shape; the chip's compiler (Mosaic) does
not.  These tests lower and compile each kernel for one chip of a
*described* v5e:2x2 topology — nothing runs, no chip is needed — so a
kernel the chip would refuse (tiling, VMEM) fails here, on the CPU.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.core import executor as X
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ops import DENSE_PAGE, paged_attention_dense
from repro.kernels.paged_attention import kv_append, paged_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.unified_pd import unified_pd
from repro.models.transformer import init_cache

PAGE = 16           # unified_pd's (Hkv, N, page, D) pages
# the benchmark's configurations at their serving shapes: (slots, slot len)
SERVED = {"starcoder2-3b": (64, 2048), "minicpm-2b": (8, 2048),
          "mellum2-12b-a2.5b": (32, 8192)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sc2():
    return get_config("starcoder2-3b")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_prefill_compiles(one_chip, sc2):
    S, D = 2048, sc2.head_dim
    q = _sds((1, sc2.num_heads, S, D), jnp.bfloat16, one_chip)
    kv = _sds((1, sc2.num_kv_heads, S, D), jnp.bfloat16, one_chip)
    c = flash_prefill.lower(q, kv, kv, block_q=512, block_k=512,
                            interpret=False).compile()
    assert _has_kernel(c)


def _decode_args(cfg, one_chip, B=8, max_seq=2048, page=DENSE_PAGE):
    """Decode over (Hkv, N, D, page) pages, tokens on the lanes."""
    D, Hkv = cfg.head_dim, cfg.num_kv_heads
    mp = max_seq // page
    q = _sds((B, cfg.num_heads, D), jnp.bfloat16, one_chip)
    pages = _sds((Hkv, B * mp, D, page), jnp.bfloat16, one_chip)
    tables = _sds((B, mp), jnp.int32, one_chip)
    lens = _sds((B,), jnp.int32, one_chip)
    return q, pages, tables, lens


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_paged_attention_compiles(one_chip, arch):
    q, pages, tables, lens = _decode_args(get_config(arch), one_chip)
    c = paged_attention.lower(q, pages, pages, tables, lens,
                              interpret=False).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_paged_attention_dense_compiles(one_chip, arch):
    """Decode attention over the served stacked cache (every period's
    layer, the benchmark's slots and slot length), in blocks of all KV
    heads and ``dense_block_tokens`` tokens; Mellum2's window rings are
    ``test_window_ring_decode_kernels_compile``'s."""
    cfg = get_config(arch)
    B, S = SERVED[arch]
    cache = _sds((cfg.num_periods, cfg.num_kv_heads, B, cfg.head_dim, S),
                 jnp.bfloat16, one_chip)
    q = _sds((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    c = jax.jit(paged_attention_dense).lower(
        q, cache, cache, _sds((), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_kv_append_compiles(one_chip, arch):
    cfg = get_config(arch)
    B, S = SERVED[arch]
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    cache = _sds((2, Hkv, B, D, S), jnp.bfloat16, one_chip)
    new = _sds((B, Hkv, D), jnp.bfloat16, one_chip)
    c = kv_append.lower(cache, cache, new, new, _sds((B,), jnp.int32,
                                                     one_chip),
                        _sds((), jnp.int32, one_chip), page=DENSE_PAGE,
                        interpret=False).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("tokens", [32, 4096])
def test_moe_gmm_compiles(one_chip, tokens):
    """The held-expert SwiGLU at Mellum2's widths (2304 x 896, 16 of 64
    experts, top-8): a decode step of 32 slots and a 4096-token
    prefill."""
    cfg = get_config("mellum2-12b-a2.5b")
    d, f, Eh = cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.num_held
    xs = _sds((tokens * cfg.moe.top_k, d), jnp.bfloat16, one_chip)
    w = _sds((Eh, d, f), jnp.bfloat16, one_chip)
    wo = _sds((Eh, f, d), jnp.bfloat16, one_chip)
    gs = _sds((Eh + 1,), jnp.int32, one_chip)
    c = moe_gmm.lower(xs, w, w, wo, gs, interpret=False).compile()
    assert _has_kernel(c)


def test_window_ring_decode_kernels_compile(one_chip):
    """``kv_append`` and ``paged_attention`` over Mellum2's window rings:
    a stacked (7, 4, 32, 128, 1024) cache at the serving shape."""
    cfg = get_config("mellum2-12b-a2.5b")
    B, Hkv, D, W = 32, cfg.num_kv_heads, cfg.head_dim, cfg.sliding_window
    ring = _sds((7, Hkv, B, D, W), jnp.bfloat16, one_chip)
    new = _sds((B, Hkv, D), jnp.bfloat16, one_chip)
    lens = _sds((B,), jnp.int32, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    c = kv_append.lower(ring, ring, new, new, lens, layer, page=DENSE_PAGE,
                        interpret=False).compile()
    assert _has_kernel(c)
    q = _sds((B, cfg.num_heads, D), jnp.bfloat16, one_chip)
    read = jax.jit(functools.partial(paged_attention_dense, window=W))
    assert _has_kernel(read.lower(q, ring, ring, layer, lens).compile())


def test_unified_pd_compiles(one_chip, sc2):
    D, Sp = sc2.head_dim, 512
    q_p = _sds((1, sc2.num_heads, Sp, D), jnp.bfloat16, one_chip)
    kv_p = _sds((1, sc2.num_kv_heads, Sp, D), jnp.bfloat16, one_chip)
    q_d, _, tables, lens = _decode_args(sc2, one_chip, page=PAGE)
    pages = _sds((sc2.num_kv_heads, tables.shape[0] * tables.shape[1],
                  PAGE, D), jnp.bfloat16, one_chip)
    c = unified_pd.lower(q_p, kv_p, kv_p, q_d, pages, pages, tables, lens,
                         f_decode=0.5, block_q=512, block_k=512,
                         interpret=False).compile()
    assert _has_kernel(c)


# an HLO value: "%name = <type> op(operands" or a computation's
# "name: <type>" parameter
_DEF = re.compile(r"%?([\w.\-]+) = (.*?) ([\w\-]+)\(([^)]*)")
_PARAM = re.compile(r"([\w.\-]+): (\w+\[[\d,]*\])")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_COPIES = ("copy", "copy-start", "copy-done", "transpose", "dynamic-slice")


def _dims(type_text):
    """The array shapes of an HLO type, each as its sorted dims above 1
    (so a relayout, a bitcast or a unit axis leaves it the same)."""
    return {tuple(sorted(int(d) for d in dims.split(",") if d not in
                         ("", "1")))
            for dims in _SHAPE.findall(type_text)}


def _slab_traffic(hlo: str, slab, cache):
    """Ops of ``hlo`` that move a layer's K/V slab (dims ``slab``) or
    the whole cache (``cache``): any value of a slab's shape, a copy,
    transpose or slice of either, and a dynamic-update-slice whose
    update is either.  An in-place column update of the cache is none
    of these."""
    slab, cache = tuple(sorted(slab)), tuple(sorted(cache))
    shapes = {}
    bad = []
    for line in hlo.splitlines():
        for name, t in _PARAM.findall(line):
            shapes[name] = _dims(t)
            if slab in shapes[name]:
                bad.append(line.strip()[:160])
        m = _DEF.search(line)
        if not m:
            continue
        name, t, op, operands = m.groups()
        shapes[name] = _dims(t)
        args = [a.strip().lstrip("%") for a in operands.split(",")]
        moved = shapes[name]
        if op == "dynamic-update-slice":
            moved = shapes.get(args[1], set())
        elif op not in _COPIES:
            moved = moved & {slab}
        if moved & {slab, cache}:
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_decode_step_updates_cache_in_place(one_chip, arch):
    """The served decode program at the benchmark's widths and slot shape
    (two periods of layers) writes each new K/V column into the stacked
    cache and the kernel reads it there: no layer's slab, full or window
    ring, is sliced out, relaid, copied or written back."""
    base = get_config(arch)
    cfg = dataclasses.replace(base, num_layers=2 * base.period)
    B, S = SERVED[arch]

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(X._init_params, cfg=cfg), X.param_key(0)))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, B, S)))
    ints = _sds((B,), jnp.int32, one_chip)
    hlo = X._decode_step.lower(params, cache, ints, ints, cfg=cfg,
                               impl="pallas").compile().as_text()
    assert "paged_attention" in hlo and "kv_append" in hlo
    for pos in range(cfg.period):
        # a window layer's slot is a ring of its window
        k = cache[f"pos{pos}"]["k"]
        assert k.shape == (2, cfg.num_kv_heads, B, cfg.head_dim,
                           min(S, cfg.window_at(pos) or S))
        assert _slab_traffic(hlo, k.shape[1:], k.shape) == []


def test_ssm_scan_compiles(one_chip):
    cfg = get_config("jamba-1.5-large-398b")
    din, ds, L = cfg.d_inner, cfg.mamba.d_state, 1024
    x = _sds((1, L, din), jnp.float32, one_chip)
    A = _sds((din, ds), jnp.float32, one_chip)
    bc = _sds((1, L, ds), jnp.float32, one_chip)
    c = ssm_scan.lower(x, x, A, bc, bc, chunk=128, tile_d=256,
                       interpret=False).compile()
    assert _has_kernel(c)
