"""A configuration, a traffic mix, a metric and a reference added as
files in a directory of their own, with no file of the benchmark
edited; and the fp8 control failing the check that sound runs pass."""
import json
import shutil

import jax
import pytest

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


DENSE = (harness.HERE / "references" / "dense.py").read_text()

# the dense reference under another name, noting each of its entries that
# the harness, the check and the metrics call
RECORDED = DENSE + '''

import pathlib as _pathlib

_CALLS = _pathlib.Path(__CALLS__)


def weight_law(path, shape, tied):
    return None


def _noted(name, fn):
    def call(*args, **kw):
        with _CALLS.open("a") as f:
            f.write(name + "\\n")
        return fn(*args, **kw)
    return call


for _name in ("dims_of", "layout", "check_registry", "weight_law",
              "forward", "decode_flops"):
    globals()[_name] = _noted(_name, globals()[_name])
'''

DECODE_WORK = '''"""The window's decode FLOPs, by the reference's counts."""


def reduce(run):
    return float(sum(run.ref.decode_flops(run.dims, s.decode_lens)
                     for s in run.steps if s.decode_lens))
'''


def _cell_with_reference(tmp_path, reference, metrics=()):
    """A cell of the tiny starcoder2 whose configuration names
    ``reference``, all its files in ``tmp_path``."""
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir(exist_ok=True)
    model = json.loads((TESTDATA / "configs" / "tiny-sc2.json").read_text())
    model["reference"] = reference
    (tmp_path / "configs" / "own-ref.json").write_text(json.dumps(model))
    shutil.copy(TESTDATA / "traffic" / "tiny-open.json",
                tmp_path / "traffic" / "own-mix.json")
    bench = json.loads((TESTDATA / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "own-ref", "source": "test",
                         "file": "new/configs/own-ref.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "own-cell", "config": "own-ref",
                           "traffic": "own-mix", "chips": 1, "why": "test"}]
    for name, text in metrics:
        (tmp_path / "metrics" / f"{name}.py").write_text(text)
        bench["end_to_end"].append({"name": name, "unit": "FLOP",
                                    "better": "higher", "bound": 0.1,
                                    "source": "host_clock"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.Files(benchmark=tmp_path / "BENCHMARK.json",
                         extra=tmp_path)


def test_a_configuration_brings_its_own_reference(tmp_path):
    (tmp_path / "references").mkdir()
    calls = tmp_path / "calls.txt"
    (tmp_path / "references" / "recorded.py").write_text(
        RECORDED.replace("__CALLS__", repr(str(calls))))
    files = _cell_with_reference(tmp_path, "recorded",
                                 [("decode_work", DECODE_WORK)])
    line = harness.run_cell("own-cell", 2 ** 31 + 5, 3.0, False,
                            files=files, devices=jax.devices("cpu"),
                            interpret=True, log=lambda *a: None)
    assert line["correct"], line["checks"]
    assert line["metrics"]["decode_work"]["value"] > 0
    # served (registry and layout), weighed (the weight law of every
    # served leaf), judged (the forward) and counted (a metric's work)
    # through the module that exists only beside the cell
    assert set(calls.read_text().split()) == {
        "dims_of", "layout", "check_registry", "weight_law", "forward",
        "decode_flops"}


def test_a_reference_that_refuses_the_program_config_is_a_setup_error(
        tmp_path):
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "refuses.py").write_text(DENSE + '''

def check_registry(cfg, dims):
    raise ValueError(f"{cfg.name}: not the block this reference computes")
''')
    files = _cell_with_reference(tmp_path, "refuses")
    with pytest.raises(harness.SetupError, match="not the block"):
        harness.build_stack("own-cell", 3, False, files=files,
                            devices=jax.devices("cpu"), interpret=True)


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
