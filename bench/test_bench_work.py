"""FLOPs and bytes of both configurations, worked out by hand, and the
table of peaks."""
import json
import pathlib

import pytest

from bench import harness, reference, work

CONFIGS = pathlib.Path(__file__).parent / "configs"


def dims(name):
    return reference.dims_of(json.loads((CONFIGS / f"{name}.json")
                                        .read_text()))


def test_starcoder2_weights_and_kv():
    d = dims("starcoder2-3b")
    # q, o: 3072 x 3072; k, v: 3072 x 256; MLP: 2 x 3072 x 12288
    assert work.layer_matmul_params(d) == 18_874_368 + 1_572_864 + \
        75_497_472
    # two norms, q/k/v biases (24 + 2 + 2) x 128
    assert work.layer_vector_params(d) == 6144 + 3584
    # 30 layers + embedding + untied head (49152 x 3072 each) + final norm
    assert work.weight_params(d) == 30 * 95_954_432 + 2 * 150_994_944 + 3072
    assert work.weight_bytes(d) == 6_361_251_840
    assert work.kv_bytes_per_token(d) == 2 * 30 * 2 * 128 * 2 == 30_720
    # weights + a dense 64 x 2048 slot cache at 819 GB/s: 12.68 ms
    assert work.decode_bound_s(d, 64, 2048, 819e9) == pytest.approx(
        (6_361_251_840 + 64 * 2048 * 30_720) / 819e9)
    assert work.decode_bound_s(d, 64, 2048, 819e9) == pytest.approx(
        12.683e-3, abs=1e-6)


def test_minicpm_weights_and_kv():
    d = dims("minicpm-2b")
    assert d.vocab_padded == 122_880
    # q, k, v, o: 2304 x 2304 each (MHA); gated MLP: 3 x 2304 x 5760
    assert work.layer_matmul_params(d) == 4 * 5_308_416 + 39_813_120
    # 40 layers, tied embedding (122880 x 2304), final norm
    assert work.weight_bytes(d) == 2 * (40 * (61_046_784 + 4608)
                                        + 283_115_520 + 2304)
    assert work.weight_bytes(d) == 5_450_347_008
    assert work.kv_bytes_per_token(d) == 2 * 40 * 36 * 64 * 2 == 368_640
    dense = 8 * 2048 * 368_640
    assert dense == 6_039_797_760
    assert work.decode_bound_s(d, 8, 2048, 819e9) == pytest.approx(
        14.029e-3, abs=1e-6)
    # KV per token is 12x starcoder2's
    assert work.kv_bytes_per_token(d) == 12 * work.kv_bytes_per_token(
        dims("starcoder2-3b"))


def test_step_work_by_hand():
    d = dims("starcoder2-3b")
    # one prompt token: 2 FLOPs per matmul weight, 30 layers; attention of
    # one query over one key (4 * 24 heads * 128 * 30 layers); head once
    assert work.prefill_flops(d, 1) == 2 * 95_944_704 * 30 + 368_640 + \
        2 * 3072 * 49_152
    # causal: query i attends i + 1 keys, so n(n+1)/2 pairs
    assert work.prefill_attention_flops(d, 4) == 368_640 * 10
    # two live slots attending 10 and 30 tokens
    assert work.decode_flops(d, [10, 30]) == 2 * (
        2 * 95_944_704 * 30 + 2 * 3072 * 49_152) + 368_640 * 40
    # live KV of those two, plus q and out of 24 heads x 128 x 30 layers
    assert work.paged_attention_bytes(d, [10, 30]) == 40 * 30_720 + \
        2 * 2 * 24 * 128 * 30 * 2


def test_peaks_by_device_kind():
    v5e = harness.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError):
        harness.peaks_of("TPU v9 imaginary")
