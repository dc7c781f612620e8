"""Per-arch smoke tests (reduced configs) + decode/forward consistency."""
import jax
import jax.numpy as jnp
import pytest

from repro.config import get_reduced_config, list_archs
from repro.models.transformer import (decode_forward, forward, greedy_sample,
                                      init_cache, init_model, lm_loss,
                                      write_prefill_to_cache)

ARCHS = list_archs()


def _inputs(cfg, rng, B, S):
    if cfg.frontend == "embed_stub":
        x = jax.random.normal(rng, (B, S, cfg.d_model), jnp.float32)
    else:
        x = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    if cfg.rope_type == "mrope":
        pos = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, 3))
    else:
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return x, pos


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(rng, arch):
    """One forward step on the reduced config: shapes + no NaNs."""
    cfg = get_reduced_config(arch)
    params, specs = init_model(rng, cfg, tp=1)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, tuple))
    B, S = 2, 16
    x, pos = _inputs(cfg, rng, B, S)
    logits = forward(params, cfg, x, pos, 1)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(rng, arch):
    """One train step: finite loss, grads flow to every layer leaf."""
    cfg = get_reduced_config(arch)
    params, _ = init_model(rng, cfg, tp=1)
    B, S = 2, 16
    x, pos = _inputs(cfg, rng, B, S)
    if cfg.frontend == "embed_stub":
        labels = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    else:
        labels = jnp.roll(x, -1, axis=1)
    loss, grads = jax.value_and_grad(lm_loss)(params, cfg, x, labels, pos)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads["layers"]))
    assert gn > 0.0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(rng, arch):
    """Prefill->cache->decode next-token logits == full forward logits."""
    cfg = get_reduced_config(arch)
    params, _ = init_model(rng, cfg, tp=1)
    B, S = 2, 16
    x, pos = _inputs(cfg, rng, B, S)
    logits, aux = forward(params, cfg, x, pos, 1, return_aux=True)
    cache = init_cache(cfg, B, 32, 1)
    cache = write_prefill_to_cache(cfg, cache, aux, S)
    seq_lens = jnp.full((B,), S, jnp.int32)
    if cfg.frontend == "embed_stub":
        nxt = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.d_model),
                                jnp.float32)
        full_in = jnp.concatenate([x, nxt], axis=1)
    else:
        nxt = greedy_sample(logits[:, -1:], cfg.vocab_size)
        full_in = jnp.concatenate([x, nxt], axis=1)
    if cfg.rope_type == "mrope":
        dpos = jnp.broadcast_to(
            jnp.full((1, 1, 1), S), (B, 1, 3)).astype(jnp.int32)
        fpos = jnp.broadcast_to(jnp.arange(S + 1)[None, :, None],
                                (B, S + 1, 3))
    else:
        dpos = jnp.full((B, 1), S, jnp.int32)
        fpos = jnp.broadcast_to(jnp.arange(S + 1)[None], (B, S + 1))
    dl, _ = decode_forward(params, cfg, nxt, dpos, cache, seq_lens, 1)
    # reference = the inference-mode forward (return_aux=True): both use
    # the no-drop MoE capacity policy; the training path drops tokens
    fl, _ = forward(params, cfg, full_in, fpos, 1, return_aux=True)
    a = dl[:, 0, :cfg.vocab_size].astype(jnp.float32)
    b = fl[:, -1, :cfg.vocab_size].astype(jnp.float32)
    # bf16 models accumulate rounding differences between the two paths;
    # compare with a scale-aware tolerance
    scale = float(jnp.std(b)) + 1e-6
    assert float(jnp.max(jnp.abs(a - b))) / scale < 0.25, arch


def test_multi_token_greedy_decode(rng):
    """Decode 6 tokens greedily == teacher-forced full forward argmax."""
    cfg = get_reduced_config("granite-8b")
    params, _ = init_model(rng, cfg, tp=1)
    B, S, T = 1, 8, 6
    x, pos = _inputs(cfg, rng, B, S)
    logits, aux = forward(params, cfg, x, pos, 1, return_aux=True)
    cache = init_cache(cfg, B, S + T + 2, 1)
    cache = write_prefill_to_cache(cfg, cache, aux, S)
    toks = [int(greedy_sample(logits[:, -1:], cfg.vocab_size)[0, 0])]
    seq = x
    seq_lens = jnp.full((B,), S, jnp.int32)
    cur = greedy_sample(logits[:, -1:], cfg.vocab_size)
    for t in range(T - 1):
        dpos = (seq_lens[:, None]).astype(jnp.int32)
        dl, cache = decode_forward(params, cfg, cur, dpos, cache,
                                   seq_lens, 1)
        seq_lens = seq_lens + 1
        cur = greedy_sample(dl, cfg.vocab_size)
        toks.append(int(cur[0, 0]))
    # teacher-forced reference (inference-mode forward)
    full = jnp.concatenate(
        [x, jnp.array(toks[:-1], jnp.int32)[None]], axis=1)
    fpos = jnp.broadcast_to(jnp.arange(full.shape[1])[None],
                            (B, full.shape[1]))
    fl, _ = forward(params, cfg, full, fpos, 1, return_aux=True)
    want = [int(t) for t in
            jnp.argmax(fl[0, S - 1:, :cfg.vocab_size], -1)]
    assert toks == want


def test_sliding_window_ring_cache(rng):
    """Mixtral ring cache: context beyond the window is evicted but
    decode still matches full forward (which also only sees the window)."""
    cfg = get_reduced_config("mixtral-8x7b")   # window 16
    params, _ = init_model(rng, cfg, tp=1)
    B, S = 1, 24   # S > window
    x, pos = _inputs(cfg, rng, B, S)
    logits, aux = forward(params, cfg, x, pos, 1, return_aux=True)
    cache = init_cache(cfg, B, 64, 1)
    assert cache["pos0"]["k"].shape[3] == cfg.sliding_window
    cache = write_prefill_to_cache(cfg, cache, aux, S)
    nxt = greedy_sample(logits[:, -1:], cfg.vocab_size)
    dl, _ = decode_forward(params, cfg, nxt,
                           jnp.full((B, 1), S, jnp.int32), cache,
                           jnp.full((B,), S, jnp.int32), 1)
    full_in = jnp.concatenate([x, nxt], axis=1)
    fpos = jnp.broadcast_to(jnp.arange(S + 1)[None], (B, S + 1))
    fl, _ = forward(params, cfg, full_in, fpos, 1, return_aux=True)
    a = dl[0, 0, :cfg.vocab_size].astype(jnp.float32)
    b = fl[0, -1, :cfg.vocab_size].astype(jnp.float32)
    scale = float(jnp.std(b)) + 1e-6
    assert float(jnp.max(jnp.abs(a - b))) / scale < 0.25


def test_param_count_sanity():
    """Analytic param counts are in the advertised ballpark."""
    from repro.config import get_config
    expect = {"llama3-70b": 70e9, "mixtral-8x7b": 47e9,
              "qwen3-moe-235b-a22b": 235e9, "granite-8b": 8e9,
              "jamba-1.5-large-398b": 398e9, "xlstm-125m": 125e6,
              "mixtral-8x22b": 141e9}
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert 0.7 * n < got < 1.35 * n, (arch, got, n)


def test_moe_active_params():
    from repro.config import get_config
    cfg = get_config("qwen3-moe-235b-a22b")
    active = cfg.active_param_count()
    assert 15e9 < active < 30e9   # the "A22B" in the name
