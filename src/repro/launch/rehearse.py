"""Compile the device executor's step programs for a described TPU chip.

Rehearses a chip run without the chip: every program ``DeviceExecutor``
calls while serving (parameter init, the prefill of each bucket, the slot
insert, the decode step) is lowered and compiled for one chip of a
described topology at full model size, and its memory analysis is
checked against the chip's HBM.  Nothing runs; no chip is needed.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.rehearse \\
        --arch starcoder2-3b --slots 8 --max-seq-len 2048
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

HBM_BYTES = 16 * 10**9          # one TPU v5e chip


def _mib(n: float) -> str:
    return f"{n / 2**20:9.1f} MiB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=2048)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.config import get_config
    from repro.core import executor as X
    from repro.models.transformer import init_cache

    # a described chip's compile cannot be read back from the persistent
    # cache without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = get_config(args.arch)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    key = X.param_key(0)
    params = on_chip(jax.eval_shape(
        functools.partial(X._init_params, cfg=cfg), key))
    cache = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, args.slots, args.max_seq_len)))
    row = sds((), jnp.int32)
    programs = [("init_params", jax.jit(
        functools.partial(X._init_params, cfg=cfg), out_shardings=chip),
        (sds(key.shape, key.dtype),), {})]
    for Lb in X.prefill_buckets(args.max_seq_len):
        programs.append((f"prefill[{Lb}]", X._prefill_step,
                         (params, sds((1, Lb), jnp.int32),
                          sds((1,), jnp.int32)),
                         dict(cfg=cfg, impl="pallas")))
        kv = on_chip(jax.eval_shape(lambda: init_cache(cfg, 1, Lb)))
        programs.append((f"insert[{Lb}]", X._insert_slot, (cache, kv, row),
                         {}))
    programs.append(("decode", X._decode_step,
                     (params, cache, sds((args.slots,), jnp.int32),
                      sds((args.slots,), jnp.int32)),
                     dict(cfg=cfg, impl="pallas")))

    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    kv_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    print(f"{cfg.name}: weights {_mib(weights)}  slot cache "
          f"{_mib(kv_bytes)}  ({args.slots} x {args.max_seq_len})")
    worst = 0.0
    for name, fn, a, kw in programs:
        t0 = time.perf_counter()
        compiled = fn.lower(*a, **kw).compile()
        dt = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        kernel = "tpu_custom_call" in compiled.as_text()
        print(f"  {name:14s} compile {dt:6.1f}s  args "
              f"{_mib(ma.argument_size_in_bytes)}  temp "
              f"{_mib(ma.temp_size_in_bytes)}  peak {_mib(live)}  "
              f"pallas={kernel}")
        worst = max(worst, live)
    print(f"largest program needs {_mib(worst)} of {_mib(HBM_BYTES)}")
    if worst > HBM_BYTES:
        print("does not fit one chip", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
