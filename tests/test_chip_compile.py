"""Compile-only checks of the Pallas kernels at real widths for TPU v5e.

Interpret mode runs any block shape; the chip's compiler (Mosaic) does
not.  These tests lower and compile each kernel for one chip of a
*described* v5e:2x2 topology — nothing runs, no chip is needed — so a
kernel the chip would refuse (tiling, VMEM) fails here, on the CPU.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.unified_pd import unified_pd

PAGE = 16


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


def _decode_args(cfg, one_chip, B=8, max_seq=2048):
    D, Hkv = cfg.head_dim, cfg.num_kv_heads
    mp = max_seq // PAGE
    q = _sds((B, cfg.num_heads, D), jnp.bfloat16, one_chip)
    pages = _sds((Hkv, B * mp, PAGE, D), jnp.bfloat16, one_chip)
    tables = _sds((B, mp), jnp.int32, one_chip)
    lens = _sds((B,), jnp.int32, one_chip)
    return q, pages, tables, lens


def test_paged_attention_compiles(one_chip, sc2):
    q, pages, tables, lens = _decode_args(sc2, one_chip)
    c = paged_attention.lower(q, pages, pages, tables, lens,
                              interpret=False).compile()
    assert _has_kernel(c)


def test_unified_pd_compiles(one_chip, sc2):
    D, Sp = sc2.head_dim, 512
    q_p = _sds((1, sc2.num_heads, Sp, D), jnp.bfloat16, one_chip)
    kv_p = _sds((1, sc2.num_kv_heads, Sp, D), jnp.bfloat16, one_chip)
    q_d, pages, tables, lens = _decode_args(sc2, one_chip)
    c = unified_pd.lower(q_p, kv_p, kv_p, q_d, pages, pages, tables, lens,
                         f_decode=0.5, block_q=512, block_k=512,
                         interpret=False).compile()
    assert _has_kernel(c)


def test_ssm_scan_compiles(one_chip):
    cfg = get_config("jamba-1.5-large-398b")
    din, ds, L = cfg.d_inner, cfg.mamba.d_state, 1024
    x = _sds((1, L, din), jnp.float32, one_chip)
    A = _sds((din, ds), jnp.float32, one_chip)
    bc = _sds((1, L, ds), jnp.float32, one_chip)
    c = ssm_scan.lower(x, x, A, bc, bc, chunk=128, tile_d=256,
                       interpret=False).compile()
    assert _has_kernel(c)
