"""FLOPs, bytes and weight layouts of both configurations through the
dense reference module, worked out by hand, and the table of peaks."""
import json
import pathlib

import pytest

from bench import harness
from bench.references import dense

CONFIGS = pathlib.Path(__file__).parent / "configs"


def dims(name):
    return dense.dims_of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_starcoder2_weights_and_kv():
    d = dims("starcoder2-3b")
    # q, o: 3072 x 3072; k, v: 3072 x 256; MLP: 2 x 3072 x 12288
    assert dense.layer_matmul_params(d) == 18_874_368 + 1_572_864 + \
        75_497_472
    # two norms, q/k/v biases (24 + 2 + 2) x 128
    assert dense.layer_vector_params(d) == 6144 + 3584
    # 30 layers + embedding + untied head (49152 x 3072 each) + final norm
    assert dense.weight_params(d) == 30 * 95_954_432 + 2 * 150_994_944 + 3072
    assert dense.weight_bytes(d) == 6_361_251_840
    assert dense.kv_bytes_per_token(d) == 2 * 30 * 2 * 128 * 2 == 30_720
    # weights + a dense 64 x 2048 slot cache at 819 GB/s: 12.68 ms
    assert dense.decode_bound_s(d, 64, 2048, 819e9) == pytest.approx(
        (6_361_251_840 + 64 * 2048 * 30_720) / 819e9)
    assert dense.decode_bound_s(d, 64, 2048, 819e9) == pytest.approx(
        12.683e-3, abs=1e-6)


def test_minicpm_weights_and_kv():
    d = dims("minicpm-2b")
    assert d.vocab_padded == 122_880
    # q, k, v, o: 2304 x 2304 each (MHA); gated MLP: 3 x 2304 x 5760
    assert dense.layer_matmul_params(d) == 4 * 5_308_416 + 39_813_120
    # 40 layers, tied embedding (122880 x 2304), final norm
    assert dense.weight_bytes(d) == 2 * (40 * (61_046_784 + 4608)
                                         + 283_115_520 + 2304)
    assert dense.weight_bytes(d) == 5_450_347_008
    assert dense.kv_bytes_per_token(d) == 2 * 40 * 36 * 64 * 2 == 368_640
    cache = 8 * 2048 * 368_640
    assert cache == 6_039_797_760
    assert dense.decode_bound_s(d, 8, 2048, 819e9) == pytest.approx(
        14.029e-3, abs=1e-6)
    # KV per token is 12x starcoder2's
    assert dense.kv_bytes_per_token(d) == 12 * dense.kv_bytes_per_token(
        dims("starcoder2-3b"))


def test_step_work_by_hand():
    d = dims("starcoder2-3b")
    # one prompt token: 2 FLOPs per matmul weight, 30 layers; attention of
    # one query over one key (4 * 24 heads * 128 * 30 layers); head once
    assert dense.prefill_flops(d, 1) == 2 * 95_944_704 * 30 + 368_640 + \
        2 * 3072 * 49_152
    # causal: query i attends i + 1 keys, so n(n+1)/2 pairs
    assert dense.prefill_attention_flops(d, 4) == 368_640 * 10
    # two live slots attending 10 and 30 tokens
    assert dense.decode_flops(d, [10, 30]) == 2 * (
        2 * 95_944_704 * 30 + 2 * 3072 * 49_152) + 368_640 * 40
    # live KV of those two, plus q and out of 24 heads x 128 x 30 layers
    assert dense.paged_attention_bytes(d, [10, 30]) == 40 * 30_720 + \
        2 * 2 * 24 * 128 * 30 * 2


def test_weight_layouts_by_hand():
    sc2 = dense.layout(dims("starcoder2-3b"))
    assert sc2 == {
        "embed/tok": (49_152, 3072), "final_norm/w": (3072,),
        "lm_head/w": (3072, 49_152),
        "layers/pos0/norm1/w": (3072,), "layers/pos0/norm2/w": (3072,),
        "layers/pos0/mixer/wq": (3072, 3072),
        "layers/pos0/mixer/wk": (3072, 256),
        "layers/pos0/mixer/wv": (3072, 256),
        "layers/pos0/mixer/wo": (3072, 3072),
        "layers/pos0/mixer/bq": (3072,), "layers/pos0/mixer/bk": (256,),
        "layers/pos0/mixer/bv": (256,),
        "layers/pos0/ffn/w_in": (3072, 12_288),
        "layers/pos0/ffn/w_out": (12_288, 3072)}
    cpm = dense.layout(dims("minicpm-2b"))
    # tied: no head of its own; vocabulary padded to 256 rows
    assert cpm == {
        "embed/tok": (122_880, 2304), "final_norm/w": (2304,),
        "layers/pos0/norm1/w": (2304,), "layers/pos0/norm2/w": (2304,),
        "layers/pos0/mixer/wq": (2304, 2304),
        "layers/pos0/mixer/wk": (2304, 2304),
        "layers/pos0/mixer/wv": (2304, 2304),
        "layers/pos0/mixer/wo": (2304, 2304),
        "layers/pos0/ffn/w_in": (2304, 5760),
        "layers/pos0/ffn/w_gate": (2304, 5760),
        "layers/pos0/ffn/w_out": (5760, 2304)}


def test_peaks_by_device_kind():
    v5e = harness.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError):
        harness.peaks_of("TPU v9 imaginary")
