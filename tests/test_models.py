"""Per-arch smoke tests (reduced configs) + decode/forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, get_reduced_config, list_archs
from repro.models import moe as moe_mod
from repro.models.layers import rope_cos_sin, yarn_parameters
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
    assert cache["pos0"]["k"].shape[4] == cfg.sliding_window
    # per kind: a period of window layers and a full one keeps a ring
    # where the layer has a window and the whole slot where it has none
    mixed = get_reduced_config("mellum2-12b-a2.5b")
    kinds = init_cache(mixed, B, 64, 1)
    assert [kinds[f"pos{p}"]["k"].shape[4] for p in range(4)] == \
        [mixed.sliding_window] * 3 + [64]
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


def test_decode_writes_each_slot_column_in_place(rng):
    """Slots at different lengths through several decode steps (the
    kernel in interpret mode): each step writes each slot's K/V column
    at ``lens[b]`` of every layer, equal to what ``forward`` computes at
    that position, and leaves every other position as it was."""
    cfg = dataclasses.replace(get_reduced_config("starcoder2-3b"),
                              dtype="float32")
    params, _ = init_model(rng, cfg, tp=1)
    lens0, T, Sc = [5, 17, 11], 3, 32
    B = len(lens0)
    seqs = [jax.random.randint(jax.random.fold_in(rng, b), (1, n), 0,
                               cfg.vocab_size) for b, n in enumerate(lens0)]
    # each slot prefilled alone, then noise past its prompt: the
    # positions decode must leave alone hold something to compare
    slots = []
    for x in seqs:
        n = x.shape[1]
        _, aux = forward(params, cfg, x, jnp.arange(n)[None], 1,
                         return_aux=True)
        slots.append(write_prefill_to_cache(cfg, init_cache(cfg, 1, Sc),
                                            aux, n))
    cache = jax.tree.map(lambda *c: jnp.concatenate(c, axis=2), *slots)
    noise = jax.random.normal(jax.random.fold_in(rng, 99),
                              cache["pos0"]["k"].shape)
    past = (jnp.arange(Sc) >= jnp.array(lens0)[:, None])[None, None, :,
                                                         None, :]
    cache = jax.tree.map(lambda c: jnp.where(past, noise, c), cache)
    before = cache
    lens = jnp.array(lens0, jnp.int32)
    toks = jnp.array([int(x[0, -1]) for x in seqs], jnp.int32)[:, None]
    fed = [[] for _ in range(B)]
    for _ in range(T):
        dl, cache = decode_forward(params, cfg, toks, lens[:, None], cache,
                                   lens, 1, impl="interpret")
        for b in range(B):
            fed[b].append(int(toks[b, 0]))
        toks = greedy_sample(dl, cfg.vocab_size)
        lens = lens + 1
    for b, x in enumerate(seqs):
        # forward over the prompt then every fed token: position
        # lens0[b] + t holds the t-th fed token's K/V
        full = jnp.concatenate([x, jnp.array(fed[b], jnp.int32)[None]], 1)
        _, aux = forward(params, cfg, full, jnp.arange(full.shape[1])[None],
                         1, return_aux=True)
        for name in ("k", "v"):
            got = cache["pos0"][name][:, :, b]          # (P, H, D, Sc)
            want = aux["pos0"][name][:, 0].transpose(0, 2, 3, 1)
            new = slice(lens0[b], lens0[b] + T)
            np.testing.assert_allclose(got[..., new], want[..., new],
                                       atol=1e-4, rtol=1e-4)
    written = ((jnp.arange(Sc) >= jnp.array(lens0)[:, None])
               & (jnp.arange(Sc) < jnp.array(lens0)[:, None] + T))
    keep = ~written[None, None, :, None, :]
    for name in ("k", "v"):
        a, b_ = cache["pos0"][name], before["pos0"][name]
        assert bool(jnp.all(jnp.where(keep, a == b_, True))), name


def test_yarn_frequencies_and_scale():
    """Mellum2's full layers: YaRN as written out here.  Correction dims
    of beta_fast 32 and beta_slow 1 rotations over 8192 positions (18
    and 35 at head dim 128, theta 500000); a linear ramp between them
    over the 64 frequencies blends theta**(-2i/D) (below the ramp) into
    it divided by the factor 16 (above); cos and sin scaled by the
    published attention factor."""
    cfg = get_config("mellum2-12b-a2.5b")
    y, D, theta = cfg.yarn, cfg.head_dim, cfg.rope_theta
    assert cfg.yarn_at(3) is y and cfg.yarn_at(0) is None

    def dim(rot):
        return D * np.log(8192 / (rot * 2 * np.pi)) / (2 * np.log(theta))
    low, high = int(np.floor(dim(32))), int(np.ceil(dim(1)))
    assert (low, high) == (18, 35)
    base = theta ** (-np.arange(0, D, 2) / D)
    ramp = np.clip((np.arange(D // 2) - low) / (high - low), 0, 1)
    want = base / 16 * ramp + base * (1 - ramp)
    inv, scale = yarn_parameters(D, theta, y)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    assert scale == pytest.approx(0.1 * np.log(16) + 1, rel=1e-6)
    # small positions: a float32 angle at thousands of radians is off
    # by ~1e-3 of its own rounding, which checks nothing of YaRN
    pos = jnp.array([[0, 1, 2, 100]])
    cos, sin = rope_cos_sin(pos, D, theta, yarn=y)
    ang = np.asarray(pos)[..., None] * want
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * scale,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang) * scale,
                               atol=2e-5)
    # window layers keep the default RoPE
    c0, _ = rope_cos_sin(pos, D, theta)
    np.testing.assert_allclose(np.asarray(c0), np.cos(
        np.asarray(pos)[..., None] * base), atol=2e-5)


@pytest.mark.parametrize("shares", [2, 4])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_held_expert_shares_sum_to_the_uncut_layer(rng, shares, impl):
    """Each chip's share of an 8-expert top-2 layer, computed by the
    held-expert layer with its slice of the banks, sums over the shares
    to the whole layer (every expert held, no token dropped).  float32,
    so only summation order differs: 1e-5."""
    base = dataclasses.replace(get_reduced_config("mellum2-12b-a2.5b"),
                               dtype="float32")
    E = base.moe.num_experts
    whole = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, held=None))
    params, _ = init_model(rng, whole, tp=1)
    p = jax.tree.map(lambda a: a[0], params["layers"]["pos0"]["ffn"])
    x = jax.random.normal(jax.random.fold_in(rng, 7), (2, 24, base.d_model))
    want = moe_mod.moe_ffn(p, whole, x, dropless=True)
    got = 0
    pairs = 0
    for c in range(shares):
        held = tuple(range(c * E // shares, (c + 1) * E // shares))
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, held=held))
        mine = dict(p, **{k: p[k][jnp.array(held)]
                          for k in ("w_gate", "w_in", "w_out")})
        y, counts = moe_mod.held_moe_ffn(mine, cfg, x, impl=impl)
        got = got + y
        pairs += int(counts.sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert pairs == 2 * 24 * base.moe.top_k       # each pair on one chip


def test_window_rings_from_padded_prompts_then_decode_past_the_window(rng):
    """A period of three window layers and a full one: prompts
    right-padded to a bucket fill each window ring from their own last
    tokens (``lengths``), and decode steps past the window (the ring
    wraps) match the full forward.  float32 on both sides: 1e-4."""
    cfg = dataclasses.replace(get_reduced_config("mellum2-12b-a2.5b"),
                              dtype="float32")
    W = cfg.sliding_window
    params, _ = init_model(rng, cfg, tp=1)
    lens, Lb, T = [45, 20], 64, 20       # one beyond the window, one not
    B = len(lens)
    seqs = jax.random.randint(jax.random.fold_in(rng, 3), (B, Lb + T), 0,
                              cfg.vocab_size)
    padded = jnp.where(jnp.arange(Lb)[None] < jnp.array(lens)[:, None],
                       seqs[:, :Lb], 0)
    pos = jnp.broadcast_to(jnp.arange(Lb)[None], (B, Lb))
    _, aux = forward(params, cfg, padded, pos, 1, impl="interpret",
                     return_aux=True, lengths=jnp.array(lens))
    one = write_prefill_to_cache(cfg, init_cache(cfg, B, Lb, 1), aux, Lb,
                                 lengths=jnp.array(lens))
    cache = init_cache(cfg, B, Lb + T, 1)
    cache = jax.tree.map(lambda c, x: c.at[..., :x.shape[-1]].set(x),
                         cache, one)
    # causal: position n of the whole sequence is what decode sees at n
    full = forward(params, cfg, seqs, jnp.broadcast_to(
        jnp.arange(Lb + T)[None], (B, Lb + T)), 1)
    step = jax.jit(decode_forward, static_argnames=("cfg", "tp", "impl"))
    cur = jnp.array(lens, jnp.int32)
    for t in range(T):
        tok = jnp.stack([seqs[b, lens[b] + t] for b in range(B)])[:, None]
        dl, cache = step(params, cfg, tok, cur[:, None], cache, cur, tp=1,
                         impl="interpret")
        want = jnp.stack([full[b, lens[b] + t] for b in range(B)])
        np.testing.assert_allclose(np.asarray(dl[:, 0]), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        cur = cur + 1
    assert max(lens) + T > 2 * W and min(lens) < W
