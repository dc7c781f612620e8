"""Sharding translation + small-mesh integration (runs on 1 CPU device)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import get_reduced_config
from repro.launch.mesh import make_mesh
from repro.sharding import (ShardingRules, make_constrain, param_sharding,
                            rules_for_mesh, spec_to_pspec)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_spec_translation(mesh):
    rules = rules_for_mesh(mesh)
    assert spec_to_pspec((None, "model"), mesh, rules) == P(None, "model")
    assert spec_to_pspec(("batch", None), mesh, rules) == P("data", None)
    assert spec_to_pspec(("expert", None, "model"), mesh, rules) == \
        P("data", None, "model")


def test_indivisible_dims_dropped(mesh):
    rules = rules_for_mesh(mesh)
    # shape 3 not divisible by any axis size > 1 -> must drop on 2-wide
    p = spec_to_pspec(("model",), mesh, rules, shape=(3,))
    assert p == P("model") or p == P(None)  # 1-wide mesh: both legal


def test_param_sharding_tree(mesh):
    cfg = get_reduced_config("granite-8b")
    from repro.models.transformer import init_model_shapes
    shapes, specs = init_model_shapes(jax.random.PRNGKey(0), cfg, tp=1)
    sh = param_sharding(specs, shapes, mesh, fsdp=True)
    assert jax.tree.structure(sh) == jax.tree.structure(shapes)


def test_fsdp_skips_small_and_expert():
    from repro.sharding import _fsdp_spec
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules()
    # small leaf untouched
    assert _fsdp_spec((None,), (64,), mesh, rules) == (None,)
    # expert leaf untouched
    s = ("expert", None, "model")
    assert _fsdp_spec(s, (128, 4096, 4096), mesh, rules) == s


def test_constrained_forward_runs(mesh):
    """forward under a (1,1) mesh with all constraints active."""
    cfg = get_reduced_config("mixtral-8x7b")
    from repro.models.transformer import init_model, forward
    params, _ = init_model(jax.random.PRNGKey(0), cfg, tp=1)
    constrain = make_constrain(mesh)
    B, S = 2, 16
    toks = jnp.zeros((B, S), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    with mesh:
        out = jax.jit(lambda p, t: forward(p, cfg, t, pos, 1,
                                           constrain=constrain))(params,
                                                                 toks)
    assert out.shape == (B, S, cfg.vocab_padded)
    assert not bool(jnp.any(jnp.isnan(out.astype(jnp.float32))))


def test_train_step_under_mesh(mesh):
    """Full train step with constraints + remat under the host mesh."""
    cfg = get_reduced_config("qwen3-moe-235b-a22b")
    from repro.training.optimizer import OptConfig
    from repro.training.train_lib import init_train_state, make_train_step
    opt = OptConfig(lr=1e-3)
    constrain = make_constrain(mesh)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(make_train_step(cfg, opt, microbatches=2,
                                   constrain=constrain))
    B, S = 4, 16
    batch = {
        "inputs": jnp.zeros((B, S), jnp.int32),
        "labels": jnp.ones((B, S), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
    }
    with mesh:
        state, m = step(state, batch)
    assert bool(jnp.isfinite(m["loss"]))
