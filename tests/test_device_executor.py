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

from repro import obs
from repro.config import ServeConfig, get_reduced_config
from repro.core import DeviceExecutor, Request, State, drive, make_engine
from repro.core.executor import prefill_buckets, prompt_ids
from repro.kernels import ops
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


def test_refuses_mixers_other_than_attention(serve, cpu):
    """Window and full attention are served; recurrent mixers are not."""
    for arch, kind in (("jamba-1.5-large-398b", "mamba"),
                       ("xlstm-125m", "lstm")):
        with pytest.raises(ValueError, match=kind):
            DeviceExecutor(get_reduced_config(arch), serve, cpu,
                           interpret=True)


def test_mellum_share_serves_past_the_window_and_matches_reference(cpu):
    """Reduced Mellum2 (two periods of three window-32 layers and a full
    one; 8 experts top-2, this chip holding 4) through the Engine:
    prompts beyond the window fill its rings, decodes wrap them, and
    every served logit matches the f32 forward of the same share.  The
    preset is float32 (a top-2 choice among 8 experts at this width
    flips on bfloat16 rounding), so only summation order differs: 1e-3
    of a logit's spread.  The routing read back with each step is in
    its span and the counters."""
    cfg = get_reduced_config("mellum2-12b-a2.5b")
    assert cfg.dtype == "float32"
    serve = ServeConfig(mode="rapid", chips=1, max_batch_slots=3,
                        max_seq_len=128, page_size=16)
    obs.reset()
    ex = _executor(cfg, serve, cpu)
    eng = make_engine("rapid", cfg, serve, executor=ex)
    reqs = _requests([(40, 6), (20, 30), (70, 9)])
    drive(eng, reqs)
    for r in reqs:
        assert r.state is State.FINISHED, r
        assert _max_logit_error(ex, cfg, r.rid) < 1e-3
    assert max(r.prompt_len for r in reqs) > 2 * cfg.sliding_window
    assert reqs[1].prompt_len + reqs[1].max_new_tokens > \
        cfg.sliding_window
    k = cfg.moe.top_k * cfg.num_layers
    done = [s for s in obs.spans() if s.name in ("rapid.prefill",
                                                   "rapid.decode")]
    assert done and all(s.attrs["expert_pairs"] >= s.attrs["expert_max"]
                        > 0 for s in done)
    assert all(0 < s.attrs["experts_live"] <= cfg.num_layers *
               cfg.moe.num_held for s in done)
    routed = sum(s.attrs.get("valid", s.attrs.get("slots", 0)) * k
                 for s in done)
    held = sum(s.attrs["expert_pairs"] for s in done)
    assert obs.counter("moe_pairs_routed") == routed
    assert obs.counter("moe_pairs_held") == held
    assert 0 < held < routed


def test_decode_counts_kv_blocks_fetched_and_spanned(cpu):
    """Reduced Mellum2 in slots of 1024: a full layer's kernel reads
    blocks of 512 tokens, two a slot, a window layer its ring of 32,
    one a slot.  A decode step over prompts of 40 and 600 and a free
    slot (which attends 1 token) fetches only the live blocks: 1 + 2 + 1
    of 6 in a full layer, 3 of 3 in a window layer."""
    cfg = get_reduced_config("mellum2-12b-a2.5b")
    serve = ServeConfig(mode="rapid", chips=1, max_batch_slots=3,
                        max_seq_len=1024, page_size=16)
    ex = DeviceExecutor(cfg, serve, cpu, seed=3, interpret=True)
    full = [f"pos{p}" for p in range(cfg.period) if not cfg.window_at(p)]
    assert {n: ops.dense_block_tokens(c["k"]) for n, c in ex.cache.items()} \
        == {n: 512 if n in full else 32 for n in ex.cache}
    reqs = _requests([(40, 4), (600, 4)])
    for r in reqs:
        ex._prefill(r)
    obs.reset()
    ex._decode(reqs)
    n_full = cfg.num_periods * len(full)
    n_window = cfg.num_layers - n_full
    read = n_full * (1 + 2 + 1) + n_window * 3
    grid = n_full * 3 * 2 + n_window * 3
    assert (obs.counter("decode_kv_blocks_read"),
            obs.counter("decode_kv_blocks_grid")) == (read, grid)
    [span] = [s for s in obs.spans() if s.name == "rapid.decode"]
    assert span.attrs["kv_blocks_read"] == read


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
