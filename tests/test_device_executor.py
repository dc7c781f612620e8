"""DeviceExecutor on the CPU: the served path with the Pallas kernels in
explicit interpret mode, on reduced starcoder2-3b.

Served logits (prefill's first token and every decode step) must match a
teacher-forced full-sequence float32 ``impl="ref"`` forward over prompt
plus served tokens — through ``Engine`` and through the ``Gateway``.
"""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, get_reduced_config
from repro.core import DeviceExecutor, Request, State, drive, make_engine
from repro.core.executor import prefill_buckets, prompt_ids
from repro.models.transformer import forward
from repro.serving import EventLoop, Gateway

# max over positions of max_v |served - ref| / std_v(ref): bf16 serving
# against a float32 reference (the bf16 bound of tests/test_models.py)
LOGIT_TOL = 0.25


@pytest.fixture(scope="module")
def cfg():
    return get_reduced_config("starcoder2-3b")


@pytest.fixture(scope="module")
def serve():
    return ServeConfig(mode="rapid", chips=1, max_batch_slots=3,
                       max_seq_len=256, page_size=16)


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[0]


def _executor(cfg, serve, cpu, **kw):
    return DeviceExecutor(cfg, serve, cpu, seed=3, interpret=True,
                          record_logits=True, **kw)


def _requests(specs):
    return [Request(rid=i, arrival=0.01 * i, prompt_len=p, max_new_tokens=n)
            for i, (p, n) in enumerate(specs)]


def _max_logit_error(ex, cfg, rid) -> float:
    """Served logits vs the f32 reference forward, scaled per position."""
    ids = ex.token_ids(rid)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), ex.params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    seq = jnp.asarray(ids[:-1], jnp.int32)[None]
    pos = jnp.arange(seq.shape[1], dtype=jnp.int32)[None]
    ref = np.asarray(forward(params32, cfg32, seq, pos, impl="ref")[0],
                     np.float32)[:, :cfg.vocab_size]
    served = ex.logits[rid]
    assert served, rid
    return max(float(np.max(np.abs(row - ref[p])) / np.std(ref[p]))
               for p, row in served.items())


SPECS = [(40, 6), (130, 5), (7, 8), (200, 4), (90, 7), (16, 1)]


def test_engine_serves_and_matches_reference(cfg, serve, cpu):
    ex = _executor(cfg, serve, cpu)
    eng = make_engine("rapid", cfg, serve, executor=ex)
    # the pool is what the slot cache holds, not the perfmodel's HBM
    assert eng.kv.allocator.num_blocks == 3 * 256 // 16
    reqs = _requests(SPECS)
    drive(eng, reqs)
    for r in reqs:
        assert r.state is State.FINISHED, r
        assert r.tokens_generated == r.max_new_tokens
        ids = ex.token_ids(r.rid)
        assert len(ids) == r.prompt_len + r.max_new_tokens
        assert ids[:r.prompt_len] == list(
            prompt_ids(3, r.rid, r.prompt_len, cfg.vocab_size))
        # one logits row per served token (prefill + each decode step)
        assert sorted(ex.logits[r.rid]) == list(
            range(r.prompt_len - 1, r.prompt_len + r.max_new_tokens - 1))
        assert _max_logit_error(ex, cfg, r.rid) < LOGIT_TOL


def test_preempted_requests_recompute_their_context(cfg, serve, cpu):
    """Four requests whose prompts fill the pool: decode growth preempts,
    the victims re-prefill prompt + emitted tokens and still match."""
    two = dataclasses.replace(serve, max_batch_slots=2)
    ex = _executor(cfg, two, cpu)
    eng = make_engine("rapid", cfg, two, executor=ex)
    reqs = _requests([(120, 100)] * 4)
    drive(eng, reqs)
    assert sum(r.preemptions for r in reqs) > 0
    for r in reqs:
        assert r.state is State.FINISHED
        assert len(ex.token_ids(r.rid)) == 220
        assert _max_logit_error(ex, cfg, r.rid) < LOGIT_TOL


def test_gateway_serves_on_device_executor(cfg, serve, cpu):
    made = []

    def factory(device):
        made.append(_executor(cfg, serve, device))
        return made[-1]

    gw = Gateway(cfg, serve, modes=("rapid",), clock=EventLoop(),
                 devices=[cpu], executor_factory=factory)
    assert len(made) == 1
    reqs = _requests(SPECS[:4])
    records, _ = gw.serve_trace(reqs)
    assert len(records) == len(reqs)
    assert all(rec.output_len == r.max_new_tokens
               for rec, r in zip(sorted(records, key=lambda x: x.rid), reqs))
    for r in reqs:
        assert _max_logit_error(made[0], cfg, r.rid) < LOGIT_TOL


def test_slot_bounds_admission(cfg, serve, cpu):
    """Prompt beyond a slot: rejected; prompt + output beyond a slot:
    output truncated so nothing is written past the slot."""
    ex = _executor(cfg, serve, cpu)
    eng = make_engine("rapid", cfg, serve, executor=ex)
    too_long, capped = _requests([(257, 4), (250, 20)])
    drive(eng, [too_long, capped])
    assert too_long.state is State.REJECTED
    assert capped.state is State.FINISHED and capped.truncated
    assert capped.prompt_len + capped.max_new_tokens - 1 == 256
    assert len(ex.token_ids(capped.rid)) == 257


def test_refuses_cpu_without_interpret(cfg, serve, cpu):
    with pytest.raises(RuntimeError, match="TPU"):
        DeviceExecutor(cfg, serve, cpu)


def test_refuses_other_schedulers(cfg, serve, cpu):
    for mode in ("hybrid", "disagg"):
        with pytest.raises(ValueError, match="rapid"):
            DeviceExecutor(cfg, dataclasses.replace(serve, mode=mode), cpu,
                           interpret=True)


def test_prefill_buckets():
    assert prefill_buckets(2048) == [128, 256, 512, 1024, 2048]
    assert prefill_buckets(3000) == [128, 256, 512, 1024, 2048, 3000]
    assert prefill_buckets(96) == [96]


def test_launcher_refuses_device_executor_without_tpu(capsys):
    from repro.launch import serve as launcher
    with pytest.raises(SystemExit):
        launcher.main(["--arch", "starcoder2-3b", "--serve", "http",
                       "--executor", "device"])
    assert "needs a TPU" in capsys.readouterr().err


def test_compile_cache_follows_env_else_fixed_checkout_path(monkeypatch):
    from repro.core import executor as X
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(X.CACHE_ENV, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert X.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads env
        monkeypatch.delenv(X.CACHE_ENV)
        assert X.configure_compile_cache() == str(X.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(X.CACHE_DIR)
        assert X.CACHE_DIR.parent == pathlib.Path(__file__).parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
