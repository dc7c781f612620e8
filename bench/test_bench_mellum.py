"""Mellum2's share as a cell: its reference module (``references/
mellum2.py``) serves, weighs, judges and counts the registry's reduced
preset through the harness on the CPU, and its routing metric reduces
on recorded spans by hand."""
import json
import shutil

import jax
import pytest

from bench import harness
from repro import obs

TESTDATA = harness.HERE / "testdata"
FULL = harness.HERE / "configs" / "mellum2-12b-a2.5b.json"
MIX = {"source": "closed-loop callers whose prompts and replies pass the "
                 "window, for tests on the CPU",
       "arrival": {"process": "closed", "clients": 3, "fill": 3},
       "block": 3,
       "prompt": {"law": "uniform", "min": 40, "max": 60},
       "output": {"law": "uniform", "min": 40, "max": 50}}


@pytest.fixture
def ref():
    return harness.Files().module("references", "mellum2")


@pytest.fixture(autouse=True)
def empty_recorder():
    obs.reset()
    yield
    obs.reset()


def _files(tmp_path):
    """A benchmark of one cell of the tiny preset, in ``tmp_path``."""
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    shutil.copy(TESTDATA / "configs" / "tiny-mellum2.json",
                tmp_path / "configs" / "tiny-mellum2.json")
    (tmp_path / "traffic" / "tiny-agent.json").write_text(json.dumps(MIX))
    bench = json.loads((TESTDATA / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-mellum2", "source": "registry",
                         "file": "bench/testdata/configs/tiny-mellum2.json",
                         "reduced": ["num_experts"], "why": "tests"}]
    bench["workloads"] = [{"name": "tiny-mellum2", "config": "tiny-mellum2",
                           "traffic": "tiny-agent", "chips": 1,
                           "why": "tests"}]
    bench["per_layer"].append({"name": "expert_load_imbalance",
                               "unit": "ratio", "better": "lower",
                               "source": "program_span",
                               "layer": "model step programs",
                               "moves": "itl_p95_ms",
                               "workloads": ["tiny-mellum2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.Files(benchmark=tmp_path / "BENCHMARK.json",
                         extra=tmp_path)


def test_the_share_is_served_weighed_and_judged_by_its_reference(tmp_path):
    files = _files(tmp_path)
    stack = harness.build_stack("tiny-mellum2", 7, False, files=files,
                                devices=jax.devices("cpu"), interpret=True)
    assert stack.ref.__file__.endswith("references/mellum2.py")
    # install_weights found the served layout equal to the reference's
    names = {n for n, _ in jax.tree_util.tree_flatten_with_path(
        stack.executors[0].params)[0]}
    assert len(names) == len(stack.ref.layout(stack.dims))
    del stack
    line = harness.run_cell("tiny-mellum2", 2 ** 31 + 7, 1.0, False,
                            files=files, devices=jax.devices("cpu"),
                            interpret=True, control=True,
                            log=lambda *a: None)
    assert line["correct"], line["checks"]
    assert line["checks"]["tokens_compared"]["value"] >= 20
    assert {"itl_p95_ms", "output_tok_s", "setup_s"} <= set(line["metrics"])
    # the fp8 control fails the limit the served share meets
    gap = line["control"]["checks"]["logit_gap"]
    assert line["control"]["correct"] is False and gap["value"] > \
        gap["limit"]


def test_published_counts(ref):
    """The reference's counts at the published widths, by hand: 241 MB
    a layer of which 198 MB are the 16 held experts, a window-aware
    slot of 161 MB at 8192 tokens, 2 KiB a token a layer."""
    dims = ref.dims_of(json.loads(FULL.read_text()))
    assert dims.kinds == ("window",) * 3 + ("full",) + dims.kinds[4:]
    assert dims.period == 4 and len(dims.held) == 16 and dims.experts == 64
    d, f = 2304, 896
    assert ref.expert_params(dims) == 3 * d * f
    layer = 2 * d * 4096 + 2 * d * 512 + d * 64 + 16 * 3 * d * f
    assert ref.layer_matmul_params(dims) == layer
    assert ref.weight_bytes(dims) == 2 * (28 * (layer + 2 * d)
                                          + 2 * 98304 * d + d)
    assert 7.6e9 < ref.weight_bytes(dims) < 7.7e9
    assert ref.slot_kv_bytes(dims, 8192) == \
        (21 * 1024 + 7 * 8192) * 2048
    # a decode query at 3000: window layers read 1024 keys, full 3000
    assert ref.paged_attention_bytes(dims, [3000]) == \
        (21 * 1024 + 7 * 3000) * 2048 + 2 * 4096 * 28 * 2
    assert ref.attention_flops(dims, [10]) == 4 * 4096 * 28 * 10
    # two pairs a token a layer on the held share (8 x 16 / 64)
    assert ref.held_pairs_per_token(dims) == 2.0
    n = 1500
    w = 1024 * 1025 // 2 + (n - 1024) * 1024
    assert ref.prefill_attention_flops(dims, n) == \
        4 * 4096 * (21 * w + 7 * n * (n + 1) // 2)


def _routed_run(ref, dims):
    """Two decode spans and one prefill span in the window, each with the
    routing it read back, and a decode span before the window."""
    for t, pairs, mx in [(0.4, 70, 30), (1.1, 64, 10), (1.2, 60, 8)]:
        obs.record("rapid.decode", int(t * 1e9), int((t + 0.05) * 1e9),
                   worker=0, live=2, slots=2, expert_pairs=pairs,
                   expert_max=mx, experts_live=50)
    obs.record("rapid.prefill", int(1.0e9), int(1.05e9), worker=0, rid=1,
               valid=100, bucket=128, expert_pairs=500, expert_max=40,
               experts_live=64)
    return harness.Run(cell={}, model={}, dims=dims, peaks={}, chips=1,
                       seconds=1.0, window=(1.0, 2.0), requests=[],
                       steps=[], setup_s=1.0, replica_of={}, ref=ref)


def test_routing_metric_by_hand(ref):
    dims = ref.dims_of(json.loads(FULL.read_text()))
    got = harness.Files().module("metrics", "expert_load_imbalance").reduce(
        _routed_run(ref, dims))
    # the window's decode spans: 10 x 16 / 64 and 8 x 16 / 60
    assert got == pytest.approx((10 * 16 / 64 + 8 * 16 / 60) / 2)


def test_routing_metric_reads_nothing_without_routing(ref):
    """A program that reads back no routing (the dense cells, or a tree
    without it) gives no reading, and does not raise."""
    dims = ref.dims_of(json.loads(FULL.read_text()))
    run = _routed_run(ref, dims)
    obs.reset()
    obs.record("rapid.decode", int(1.1e9), int(1.2e9), worker=0, live=2,
               slots=2)
    metric = harness.Files().module("metrics", "expert_load_imbalance")
    assert metric.reduce(run) is None
