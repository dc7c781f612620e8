"""A configuration, a traffic mix and a metric added as files in a
directory of their own, with no file of the benchmark edited; and the
fp8 control failing the check that sound runs pass."""
import json
import shutil

import jax

from bench import harness

TESTDATA = harness.HERE / "testdata"

METRIC = '''"""Tokens the window's requests were streamed, all told."""


def reduce(run):
    return float(sum(len(r["tokens"]) for r in run.due_in_window()))
'''


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    shutil.copy(TESTDATA / "configs" / "tiny-cpm.json",
                tmp_path / "configs" / "new-model.json")
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps({
        "source": "test",
        "arrival": {"process": "closed", "clients": 3, "fill": 2},
        "prompt": {"law": "uniform", "min": 8, "max": 24},
        "output": {"law": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 4, "max": 16}}))
    (tmp_path / "metrics" / "tokens_streamed.py").write_text(METRIC)
    bench = json.loads((TESTDATA / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "new-model", "source": "test",
                         "file": "new/configs/new-model.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "new-cell", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "test"}]
    bench["end_to_end"].append({"name": "tokens_streamed", "unit": "tokens",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    files = harness.Files(benchmark=tmp_path / "BENCHMARK.json",
                          extra=tmp_path)
    line = harness.run_cell("new-cell", 5, 3.0, False, files=files,
                            devices=jax.devices("cpu"), interpret=True,
                            log=lambda *a: None)
    assert line["correct"], line["checks"]
    assert line["metrics"]["tokens_streamed"]["value"] > 0
    assert "output_tok_s" in line["metrics"]


def test_the_fp8_control_fails_where_the_program_passes():
    files = harness.Files(benchmark=TESTDATA / "BENCHMARK.json",
                          extra=TESTDATA)
    for seed in (11, 2 ** 31 + 14):
        line = harness.run_cell("tiny-cpm", seed, 3.0, False, files=files,
                                devices=jax.devices("cpu"), interpret=True,
                                control=True, log=lambda *a: None)
        assert line["correct"], line["checks"]
        # the control is judged by the same limits, and fails them
        ctrl = line["control"]
        assert ctrl["correct"] is False, seed
        gap = ctrl["checks"]["logit_gap"]
        assert gap["value"] > gap["limit"], seed
