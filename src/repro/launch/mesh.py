"""Meshes.  FUNCTIONS (not module-level constants) so that importing this
module never touches jax device state — the dry-run sets XLA_FLAGS before
any jax initialization."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.  The sharding rules
    (sharding.py) place arrays with ``with_sharding_constraint``, which
    only accepts Auto axes; JAX's default is Explicit."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod: 2 pods = 512 chips.

    Axes: "model" = TP inside a pod (ICI); "data" = DP/FSDP inside a pod
    (ICI); "pod" = outermost DP across pods (DCN) — parameter all-gathers
    never cross the pod boundary (sharding.py rules).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(tp: int = 1):
    """Single-process mesh for CPU examples/tests (1 device)."""
    n = len(jax.devices())
    tp = min(tp, n)
    return make_mesh((n // tp, tp), ("data", "model"))
