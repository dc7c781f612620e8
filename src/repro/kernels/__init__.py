"""Pallas TPU kernels for the perf-critical attention/scan hot-spots.

Each kernel has: <name>.py (pl.pallas_call + BlockSpec), a jit'd wrapper
in ops.py (``moe_gmm`` is its own jit'd entry point over megablox's grouped
matmul), and a pure-jnp oracle in ref.py; all validated interpret=True
on CPU (tests/test_kernels.py) and targeted at TPU v5e.
"""
from repro.kernels import ops, ref  # noqa: F401
