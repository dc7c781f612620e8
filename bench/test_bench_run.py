"""Whole runs of a cell on the CPU: the registry's reduced starcoder2-3b
with the kernels in interpret mode, served through the gateway's HTTP
server to the load generator, and checked against the reference.  The
harness's look for a chip is skipped by handing it CPU devices."""
import jax
import pytest

from bench import harness

TESTDATA = harness.HERE / "testdata"
FILES = harness.Files(benchmark=TESTDATA / "BENCHMARK.json", extra=TESTDATA)


CHIPS = {"tiny": 1, "tiny-fleet": 4}


def run(seed, trace=False, cell="tiny", **kw):
    # a fleet's replicas share the one CPU device
    return harness.run_cell(cell, seed, 3.0, trace, files=FILES,
                            devices=jax.devices("cpu")[:1] * CHIPS[cell],
                            interpret=True, log=lambda *a: None, **kw)


def test_a_sound_run_is_correct_and_reports_its_metrics():
    line = run(2 ** 31 + 21)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 6 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                    "output_tok_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["tokens_compared"]["value"] >= 20


def test_a_fleet_run_checks_every_replica():
    line = run(2 ** 31 + 23, cell="tiny-fleet")
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert line["checks"]["replicas_compared"] == {"value": 4, "limit": 4}
    assert line["failed"] == 0


def test_replies_cut_at_the_close_are_compared_as_far_as_they_streamed():
    # three callers on four slots, replies longer than the window: none
    # finishes, so every row compared is a reply cut at the close
    line = harness.run_cell("tiny-closed", 2 ** 31 + 9, 0.5, False,
                            files=FILES, devices=jax.devices("cpu"),
                            interpret=True, log=lambda *a: None)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 3 and line["failed"] == 0
    assert line["checks"]["tokens_compared"]["value"] >= 20


def _alter_decode(monkeypatch):
    from repro.core import executor
    inner = executor._decode_step

    def broken(params, cache, tokens, lens, *, cfg, impl):
        nxt, logits, cache = inner(params, cache, tokens, lens, cfg=cfg,
                                   impl=impl)
        return (nxt + 1) % cfg.vocab_size, logits, cache
    monkeypatch.setattr(executor, "_decode_step", broken)


def _alter_prefill(monkeypatch):
    from repro.core import executor
    inner = executor._prefill_step

    def broken(params, tokens, length, *, cfg, impl):
        tok, logits, kv = inner(params, tokens, length, cfg=cfg, impl=impl)
        return (tok + 7) % cfg.vocab_size, logits, kv
    monkeypatch.setattr(executor, "_prefill_step", broken)


@pytest.mark.parametrize("fault", [_alter_decode, _alter_prefill])
def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    line = run(2 ** 31 + 21)
    assert not line["correct"]
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [_alter_decode, _alter_prefill])
def test_a_token_altered_in_a_fleet_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    line = run(2 ** 31 + 23, cell="tiny-fleet")
    assert not line["correct"]
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
