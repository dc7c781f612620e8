"""The harness's arithmetic and traffic, on the CPU with no model."""
import collections
import json
import pathlib

import pytest

from bench import harness, traffic

HERE = pathlib.Path(__file__).parent
METRICS = harness.Files()


def mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["code-completion", "agent-decode"])
def test_traffic_is_fixed_by_the_seed(name):
    a = traffic.build(mix(name), 2 ** 31 + 11, 30.0)
    b = traffic.build(mix(name), 2 ** 31 + 11, 30.0)
    c = traffic.build(mix(name), 7, 30.0)
    assert a == b
    assert a["requests"] != c["requests"]
    # another seed sends the same work in another order
    sizes = lambda p: collections.Counter((q[1], q[2]) for q in p["requests"])
    assert sizes(a) == sizes(c)
    m = mix(name)
    for _, p, g in a["requests"]:
        assert m["prompt"]["min"] <= p <= m["prompt"]["max"]
        assert m["output"]["min"] <= g <= m["output"]["max"]


def test_every_block_of_requests_spans_the_length_law():
    m = mix("code-completion")
    block = m["block"]
    plan = traffic.build(m, 2 ** 31 + 17, 51.0)
    prompts = [q[1] for q in plan["requests"]]
    ranked = sorted(prompts)
    n = len(prompts)
    strata = [ranked[s[0]:s[-1] + 1] for s in
              traffic.np.array_split(traffic.np.arange(n), block)]
    for k in range(0, n - n % block, block):
        blk = sorted(prompts[k:k + block])
        # one prompt from each stratum of the sorted law
        for v, s in zip(blk, strata):
            assert s[0] <= v <= s[-1], (k, blk)


def test_open_loop_arrivals():
    m = mix("code-completion")
    rate, lead = m["arrival"]["rate_per_s"], m["arrival"]["lead_s"]
    for seed in (1, 2, 2 ** 31 + 3):
        offs = [q[0] for q in traffic.build(m, seed, 30.0)["requests"]]
        assert offs == sorted(offs)
        assert sum(1 for o in offs if 0 <= o < 30) == round(rate * 30)
        assert sum(1 for o in offs if o < 0) == round(rate * lead)
        assert offs[0] == -lead and offs[-1] < 30


def _run(requests, window=(100.0, 110.0), steps=()):
    return harness.Run(cell={}, model={}, dims=None, peaks={}, chips=1,
                       seconds=window[1] - window[0], window=window,
                       requests=requests, steps=list(steps), setup_s=12.5,
                       replica_of={})


def _steady(stall=0.0, every=0.5):
    """A request due every 0.5 s from t=100, first token 0.2 s after it
    is due, then one token every 0.05 s, 20 tokens.  With ``stall``, the
    server halts for that long every ``every`` seconds from t=100 (a
    host pause recurring through the window)."""
    shift = lambda t: t + stall * int((t - 100.0) // every)
    reqs = []
    for i in range(20):
        due = 100.0 + 0.5 * i
        toks = [shift(due + 0.2 + 0.05 * k) for k in range(20)]
        reqs.append({"i": i, "rid": i, "due": due, "tokens": toks,
                     "end": "finished", "end_t": toks[-1],
                     "prompt_len": 10, "output_len": 20})
    return reqs


def reduce(name, run):
    return METRICS.module("metrics", name).reduce(run)


def test_end_to_end_arithmetic_by_hand():
    run = _run(_steady())
    assert reduce("ttft_p50_ms", run) == pytest.approx(200.0)
    assert reduce("ttft_tail_p95_ms", run) == pytest.approx(200.0)
    assert reduce("itl_p95_ms", run) == pytest.approx(50.0)
    # tokens that arrived in [100, 110): all of request i arrive before
    # 110 while 0.5 i + 0.2 + 0.95 < 10, i.e. i <= 17; 18 and 19 partly
    n = sum(1 for r in run.requests for t in r["tokens"] if t < 110.0)
    assert reduce("output_tok_s", run) == pytest.approx(n / 10.0)
    assert reduce("setup_s", run) == 12.5
    assert harness.percentile([1, 2, 3, 4], 50) == 2.5
    assert harness.percentile([5.0], 95) == 5.0


def test_a_stall_moves_every_end_to_end_metric():
    base = _run(_steady())
    stalled = _run(_steady(stall=0.2))
    for name in ("ttft_p50_ms", "ttft_tail_p95_ms", "itl_p95_ms"):
        assert reduce(name, stalled) > reduce(name, base) * 1.2, name
    # request i waits 0.2 + 0.2 i s: the median of i = 0..19 and the 95th
    assert reduce("ttft_p50_ms", stalled) == pytest.approx(2100.0)
    assert reduce("ttft_tail_p95_ms", stalled) == pytest.approx(3810.0)
    assert reduce("output_tok_s", stalled) < reduce("output_tok_s", base)


def test_per_layer_arithmetic_by_hand():
    S = harness.Step
    steps = [S(0, 0, 100.0, 100.3, [(0, 1000)], [0], [1001], 0.1, 0.3),
             S(1, 0, 100.35, 100.45, [], [0], [1002], None, 0.1),
             S(2, 0, 100.5, 100.6, [(1, 500)], [], [], 0.1, None)]
    reqs = [{"rid": 0, "due": 99.9, "tokens": [100.2], "end": "finished"},
            {"rid": 1, "due": 100.45, "tokens": [100.6], "end": "finished"}]
    run = _run(reqs, window=(100.0, 101.0), steps=steps)
    # (0.1 + 0.1) s over 1500 tokens
    assert reduce("prefill_ms_per_ktok", run) == pytest.approx(
        0.2 / 1500 * 1e6)
    # decode: 0.3 - 0.1 and 0.1
    assert reduce("decode_step_ms", run) == pytest.approx(150.0)
    # one gap (0.05 s) while request 0 kept decoding, over three steps
    assert reduce("engine_gap_ms", run) == pytest.approx(50.0 / 3)
    # due 99.9 is outside the window; request 1 waited 0.05 s
    assert reduce("queue_wait_p95_ms", run) == pytest.approx(50.0)
    assert reduce("idle_share", run) is None      # no trace was taken
    assert reduce("replica_imbalance", run) is None   # one replica


def test_replica_imbalance():
    reqs = [{"rid": i, "due": 100.0 + i * 0.1, "tokens": [], "end": None}
            for i in range(8)]
    run = _run(reqs)
    run.chips = 4
    run.replica_of = {i: (0 if i < 5 else i - 4) for i in range(8)}
    assert reduce("replica_imbalance", run) == pytest.approx(5 / 2)


def test_unknown_cell_and_files_are_refused():
    with pytest.raises(harness.SetupError):
        harness.cell_of(harness.Files(), "no-such-cell")
    with pytest.raises(harness.SetupError):
        harness.Files().find("traffic", "no-such-mix", ".json")


def test_benchmark_names_files_for_every_entry():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    files = harness.Files()
    for c in bench["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        model = json.loads((harness.ROOT / c["file"]).read_text())
        ref = harness.reference_of(files, model)
        for entry in ("dims_of", "layout", "check_registry", "forward"):
            assert callable(getattr(ref, entry)), (c["name"], entry)
    for w in bench["workloads"]:
        files.find("traffic", w["traffic"], ".json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(files.module("metrics", m["name"]).reduce)
