"""Training substrate: descent, checkpoint/restart, elastic reshard,
gradient compression, data-pipeline determinism."""
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import get_reduced_config
from repro.data import TokenPipeline
from repro.training.checkpoint import (CheckpointManager, restore_checkpoint,
                                       save_checkpoint)
from repro.training.compression import (compress_gradients,
                                        decompress_gradients)
from repro.training.optimizer import OptConfig, wsd_schedule
from repro.training.resilience import (FailureEvent, HeartbeatMonitor,
                                       StragglerDetector, TrainingSupervisor)
from repro.training.train_lib import init_train_state, make_train_step

CFG = get_reduced_config("granite-8b")
OPT = OptConfig(lr=3e-3, warmup_steps=5, stable_steps=100, decay_steps=10)


def _batch(pipe, B, S):
    x, y = pipe.next_batch()
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return {"inputs": jnp.asarray(x), "labels": jnp.asarray(y),
            "positions": pos}


def test_loss_descends(rng):
    state = init_train_state(rng, CFG, OPT)
    step = jax.jit(make_train_step(CFG, OPT, microbatches=2))
    pipe = TokenPipeline(CFG.vocab_size, 4, 32, seed=0)
    losses = []
    for _ in range(25):
        state, m = step(state, _batch(pipe, 4, 32))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5
    assert all(np.isfinite(losses))


def test_microbatching_equivalence(rng):
    """mb=1 and mb=4 produce (nearly) identical updates."""
    s1 = init_train_state(rng, CFG, OPT)
    s2 = init_train_state(rng, CFG, OPT)
    pipe = TokenPipeline(CFG.vocab_size, 4, 32, seed=3)
    batch = _batch(pipe, 4, 32)
    f1 = jax.jit(make_train_step(CFG, OPT, microbatches=1))
    f4 = jax.jit(make_train_step(CFG, OPT, microbatches=4))
    s1, m1 = f1(s1, batch)
    s2, m4 = f4(s2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=2e-2)
    d = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
        s1.params, s2.params)
    assert max(jax.tree.leaves(d)) < 0.1   # bf16 params, small drift


def test_wsd_schedule():
    opt = OptConfig(lr=1.0, warmup_steps=10, stable_steps=100,
                    decay_steps=50, min_lr_frac=0.1)
    assert float(wsd_schedule(5, opt)) == pytest.approx(0.5)
    assert float(wsd_schedule(50, opt)) == pytest.approx(1.0)
    assert float(wsd_schedule(160, opt)) == pytest.approx(0.1, abs=1e-6)


def test_checkpoint_roundtrip(rng):
    state = init_train_state(rng, CFG, OPT)
    d = tempfile.mkdtemp()
    try:
        save_checkpoint(d, 7, state)
        restored = restore_checkpoint(d, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        shutil.rmtree(d)


def test_checkpoint_atomic_and_retention(rng):
    state = init_train_state(rng, CFG, OPT)
    d = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(d, keep=2, async_save=True)
        for s in (1, 2, 3, 4):
            mgr.save(s, state)
        mgr.wait()
        kept = sorted(x for x in os.listdir(d) if x.startswith("step_"))
        assert kept == ["step_00000003", "step_00000004"]
        assert not any(x.endswith(".tmp") for x in os.listdir(d))
    finally:
        shutil.rmtree(d)


def test_failure_restart_continuity(rng):
    """Supervisor restarts from the checkpoint and final loss still
    descends below the pre-failure level."""
    state = init_train_state(rng, CFG, OPT)
    step = jax.jit(make_train_step(CFG, OPT, microbatches=1))
    d = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(d, keep=2, async_save=False)
        sup = TrainingSupervisor(step, mgr, ckpt_every=5)
        pipe = TokenPipeline(CFG.vocab_size, 4, 32, seed=1)
        batches = [_batch(pipe, 4, 32) for _ in range(20)]
        out = sup.run(state, batches, failures=[FailureEvent(step=12)])
        assert sup.restarts == 1
        steps = [e for e in sup.log if e["event"] == "step"]
        assert steps[-1]["loss"] < steps[0]["loss"]
        assert int(out.step) >= 15
    finally:
        shutil.rmtree(d)


def test_elastic_restore_changes_placement(rng):
    """Restore under a different sharding (elastic mesh change)."""
    state = init_train_state(rng, CFG, OPT)
    d = tempfile.mkdtemp()
    try:
        save_checkpoint(d, 1, state)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), state)
        restored = restore_checkpoint(d, state, shardings=shardings)
        leaf = jax.tree.leaves(restored)[0]
        assert isinstance(leaf.sharding, NamedSharding)
    finally:
        shutil.rmtree(d)


# ---------------------------------------------------------------------------
# resilience primitives
# ---------------------------------------------------------------------------


def test_heartbeat_monitor():
    t = [0.0]
    mon = HeartbeatMonitor(["w0", "w1"], timeout_s=10, clock=lambda: t[0])
    t[0] = 5.0
    mon.beat("w0")
    t[0] = 12.0
    assert mon.dead_workers() == ["w1"]


def test_straggler_detector():
    det = StragglerDetector(threshold=1.5, patience=2)
    assert det.observe({"a": 1.0, "b": 1.0, "c": 2.0}) == []
    assert det.observe({"a": 1.0, "b": 1.0, "c": 2.0}) == ["c"]
    assert det.observe({"a": 1.0, "b": 1.0, "c": 1.0}) == []


# ---------------------------------------------------------------------------
# compression + data pipeline
# ---------------------------------------------------------------------------


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([17, 256, 1000, 4096]))
@settings(max_examples=30, deadline=None)
def test_compression_bounded_error(seed, n):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)
    out = decompress_gradients(compress_gradients({"g": g}))["g"]
    assert out.shape == g.shape
    err = float(jnp.max(jnp.abs(out - g)))
    assert err <= float(jnp.max(jnp.abs(g))) / 127.0 + 1e-7


def test_training_with_compression_descends(rng):
    state = init_train_state(rng, CFG, OPT)
    step = jax.jit(make_train_step(CFG, OPT, microbatches=1,
                                   compress_grads=True))
    pipe = TokenPipeline(CFG.vocab_size, 4, 32, seed=2)
    losses = []
    for _ in range(15):
        state, m = step(state, _batch(pipe, 4, 32))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3


@given(st.integers(0, 100), st.integers(1, 20))
@settings(max_examples=20, deadline=None)
def test_pipeline_restore_exact(start, n):
    """After restore(state) the stream continues identically."""
    p1 = TokenPipeline(1000, 2, 16, seed=9)
    for _ in range(start):
        p1.next_batch()
    snap = p1.state
    want = [p1.next_batch() for _ in range(n)]
    p2 = TokenPipeline(1000, 2, 16, seed=9)
    p2.restore(snap)
    got = [p2.next_batch() for _ in range(n)]
    for (a1, b1), (a2, b2) in zip(want, got):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)
