"""The metrics that read the program's own spans (``repro.obs``): by
hand on a made-up run, on the served path of the registry's reduced
starcoder2-3b on the CPU (kernels in interpret mode), and on a small
trace recorded on a TPU v5 lite (``testdata/spans.xplane.pb`` with the
ring and the steps of the same run in ``testdata/spans.json``, made by
``testdata/record_spans.py``)."""
import json
import pathlib

import jax
import pytest

from bench import harness
from bench import program_spans as P
from bench import trace as T
from repro import obs

TESTDATA = pathlib.Path(__file__).parent / "testdata"
FILES = harness.Files(benchmark=TESTDATA / "BENCHMARK.json", extra=TESTDATA)
NEW = ["intake_wait_p95_ms", "sched_wait_p95_ms", "prefill_pad_share",
       "decode_free_slot_share", "dispatch_idle_ms", "loop_idle_ms"]


@pytest.fixture(autouse=True)
def empty_recorder():
    obs.reset()
    yield
    obs.reset()


def reduce(name, run):
    return harness.Files().module("metrics", name).reduce(run)


def _run(window=(100.0, 110.0), requests=(), steps=(), trace=None):
    return harness.Run(cell={}, model={}, dims=None, peaks={}, chips=1,
                       seconds=window[1] - window[0], window=window,
                       requests=list(requests), steps=list(steps),
                       setup_s=1.0, replica_of={}, trace=trace)


def ns(t):
    return int(round(t * 1e9))


def _host_spans():
    """Requests 1-3 due in [100, 110), request 0 before it."""
    for rid, sub in [(0, 99.05), (1, 100.01), (2, 101.2), (3, 109.6)]:
        obs.record("rapid.submit", ns(sub), ns(sub + 0.001), rid=rid,
                   worker=0)
    for t0, t1 in [(99.5, 99.9), (100.01, 100.31), (101.2, 101.3),
                   (109.6, 110.5)]:
        obs.record("rapid.queue", ns(t0), ns(t1), rid=0, worker=0,
                   preempted=0)
    for t, valid, bucket in [(100.4, 100, 128), (101.4, 500, 512),
                             (110.2, 1, 2048)]:
        obs.record("rapid.prefill", ns(t), ns(t + 0.05), rid=0, worker=0,
                   valid=valid, bucket=bucket)
    for t, live in [(100.5, 3), (100.6, 1), (111.0, 0)]:
        obs.record("rapid.decode", ns(t), ns(t + 0.05), worker=0,
                   live=live, slots=4)
    return _run(requests=[{"rid": rid, "due": due} for rid, due in
                          [(0, 99.0), (1, 100.0), (2, 101.0), (3, 109.5)]])


def test_host_metrics_by_hand():
    run = _host_spans()
    # requests 1-3 waited 10, 200 and 100 ms for the gateway to read them
    assert reduce("intake_wait_p95_ms", run) == pytest.approx(190.0)
    # queue spans that start in the window: 300, 100 and 900 ms
    assert reduce("sched_wait_p95_ms", run) == pytest.approx(840.0)
    # 28 + 12 padded of 128 + 512 computed
    assert reduce("prefill_pad_share", run) == pytest.approx(40 / 640 * 100)
    # 1 + 3 free of 8 slot-steps
    assert reduce("decode_free_slot_share", run) == pytest.approx(50.0)
    # untraced: nothing for the idle metrics
    assert reduce("dispatch_idle_ms", run) is None
    assert reduce("loop_idle_ms", run) is None


def test_host_metrics_read_the_host_span_of_a_traced_run():
    run = _host_spans()
    run.host_window = (100.0, 101.0)
    assert reduce("intake_wait_p95_ms", run) == pytest.approx(10.0)
    assert reduce("sched_wait_p95_ms", run) == pytest.approx(300.0)
    assert reduce("prefill_pad_share", run) == pytest.approx(28 / 128 * 100)


def test_no_recorder_or_a_dropped_start_reads_nothing(monkeypatch):
    run = _host_spans()
    monkeypatch.setattr(P, "recorder", lambda: None)
    for name in NEW:
        assert reduce(name, run) is None, name
    monkeypatch.undo()
    monkeypatch.setattr(obs, "_dropped_until", ns(100.5))
    for name in NEW[:4]:
        assert reduce(name, run) is None, name
    obs.reset()
    assert all(reduce(name, _run()) is None for name in NEW)


US = 1000.0                     # ns
OFF = 7e12                      # the profile's clock less the monotonic


def _traced():
    """Two steps 2 ms apart, each on the monotonic clock from t0 (1 s,
    then 1.002 s), in microseconds from t0: ``rapid.execute`` 100-1000
    holding ``rapid.decode`` 200-900, itself holding ``rapid.put``
    200-300 and ``rapid.sync`` 600-900; ``rapid.emit`` 1100-1200.  The
    device runs 300-600.  The slice is the two steps' 4 ms."""
    ops, spans, steps = [], [], []
    for k in range(2):
        t0 = 1e9 + k * 2000 * US
        steps.append(harness.Step(k, 0, t0 * 1e-9, (t0 + 1000 * US) * 1e-9,
                                  [], [0], [10], None, 0.001))
        spans.append(T.Span(t0 + OFF, t0 + OFF + 1000 * US, "bench.execute",
                            {"step": str(k), "chip": "0"}))
        ops.append(T.Op(t0 + OFF + 300 * US, t0 + OFF + 600 * US, "fusion"))
        for name, a, b, kw in [
                ("rapid.put", 200, 300, {}), ("rapid.sync", 600, 900, {}),
                ("rapid.decode", 200, 900, {"live": 1, "slots": 4}),
                ("rapid.execute", 100, 1000, {"step": k + 1}),
                ("rapid.emit", 1100, 1200, {"tokens": 1})]:
            obs.record(name, int(t0 + a * US), int(t0 + b * US), worker=0,
                       **kw)
    spans.append(T.Span(1e9 + OFF, 1e9 + OFF + 4000 * US, "bench.window",
                        {}))
    tr = T.Trace({0: ops}, {0: []}, spans)
    T._self_times(tr.ops[0])
    return _run(window=(0.5, 1.5), steps=steps, trace=tr)


def test_idle_metrics_by_hand():
    run = _traced()
    split = P.idle_split(run)
    assert split["offset_spread_ns"] == 0 and split["executes"] == 2
    by = {k: v / US for k, v in split["by_span"].items()}
    # per step: put 100, sync 300, execute's own 100 + 100 inside; none
    # 100 before, 100 between execute and emit, 800 after; emit 100
    assert by == pytest.approx({"put": 200, "sync": 600, "execute": 400,
                                "none": 2000, "emit": 200})
    assert reduce("dispatch_idle_ms", run) == pytest.approx(0.6)
    assert reduce("loop_idle_ms", run) == pytest.approx(1.1)
    idle = 4000 * US - T.busy_ns(run.trace, 0, *run.trace.window())
    assert (split["inside"] + split["outside"]) == pytest.approx(idle)


def test_the_clock_offset_is_the_median_over_steps():
    run = _traced()
    run.trace.spans[1].start += 40 * US        # step 1's annotation late
    assert sorted(P.offsets(run)) == pytest.approx([OFF, OFF + 40 * US])
    assert P.idle_split(run)["offset_spread_ns"] == pytest.approx(40 * US)


# -- the served path on the CPU ----------------------------------------------------

@pytest.fixture(scope="module")
def stack():
    return harness.build_stack("tiny", 2 ** 31 + 5, False, files=FILES,
                               devices=jax.devices("cpu"), interpret=True)


def test_one_served_request_leaves_every_span_and_count(stack):
    plan = {"mode": "open", "requests": [[0.0, 24, 5]], "seconds": 0.5,
            "lead_s": 0.0}
    result = harness.serve_plan(stack, plan)
    (req,) = result["requests"]
    assert len(req["tokens"]) == 5
    got = obs.spans()
    names = {s.name for s in got}
    assert {"rapid.submit", "rapid.queue", "rapid.prefill", "rapid.insert",
            "rapid.decode", "rapid.put", "rapid.sync", "rapid.emit",
            "rapid.step", "rapid.schedule", "rapid.execute"} <= names
    rids = {s.attrs["rid"] for s in got if "rid" in s.attrs}
    assert rids == {req["rid"]}
    assert all(s.attrs.get("worker") == 0 for s in got)
    prefill = [s for s in got if s.name == "rapid.prefill"]
    assert [(s.attrs["valid"], s.attrs["bucket"]) for s in prefill] == \
        [(24, 128)]
    decodes = [s for s in got if s.name == "rapid.decode"]
    assert [(s.attrs["live"], s.attrs["slots"]) for s in decodes] == \
        [(1, 4)] * 4
    (queue,) = [s for s in got if s.name == "rapid.queue"]
    assert queue.end == prefill[0].start and queue.attrs["preempted"] == 0
    # one token from the prefill, the other four from decode steps
    assert sum(s.attrs["tokens"] for s in got if s.name == "rapid.emit") == 5
    c = obs.counters()
    assert (c["prefill_tokens_valid"], c["prefill_tokens_padded"]) == \
        (24, 104)
    assert (c["decode_slot_steps_live"], c["decode_slot_steps_free"]) == \
        (4, 12)
    assert c["steps"] == sum(1 for s in got if s.name == "rapid.execute") \
        == 5
    assert c["compiles"] == 0                    # warm before serving
    assert stack.gateway.metrics_summary()["counters"] == c


def test_a_traced_run_reports_the_new_metrics(tmp_path):
    bench = json.loads((TESTDATA / "BENCHMARK.json").read_text())
    mine = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] += [dict(m, workloads=["tiny"])
                           for m in mine["per_layer"] if m["name"] in NEW]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    files = harness.Files(benchmark=tmp_path / "BENCHMARK.json",
                          extra=TESTDATA)
    line = harness.run_cell("tiny", 2 ** 31 + 21, 3.0, True, files=files,
                            devices=jax.devices("cpu"), interpret=True,
                            log=lambda *a: None)
    assert line["correct"], line["checks"]
    got = line["metrics"]
    for name in NEW[:4]:
        assert name in got, name
    assert 0 < got["prefill_pad_share"]["value"] < 100
    assert 0 < got["decode_free_slot_share"]["value"] < 100
    assert got["intake_wait_p95_ms"]["value"] >= 0


# -- a trace recorded on the chip --------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    rec = json.loads((TESTDATA / "spans.json").read_text())
    steps = [harness.Step(i, c, t0, t1, [], [], [], None, None)
             for i, c, t0, t1 in rec["steps"]]
    run = _run(window=tuple(rec["window"]), steps=steps,
               trace=T.load(str(TESTDATA / "spans.xplane.pb")))
    return run, rec


def _fill_ring(rec):
    for name, start, end, attrs in rec["spans"]:
        obs.record(name, start, end, **attrs)


def test_recorded_clock_offsets_agree_within_100_us(recorded):
    run, _ = recorded
    offs = P.offsets(run)
    assert len(offs) >= 5
    assert max(offs) - min(offs) < 100 * US


def test_recorded_spans_land_on_their_profile_twins(recorded):
    run, rec = recorded
    _fill_ring(rec)
    off = sorted(P.offsets(run))[len(P.offsets(run)) // 2]
    twins = P.profile_spans(str(TESTDATA / "spans.xplane.pb"))
    assert {s.name for s in twins} >= {"rapid.execute", "rapid.decode",
                                       "rapid.put", "rapid.sync"}
    errs = P.twin_errors(obs.spans(), twins, off)
    assert len(errs) >= 5
    assert max(errs) < 100 * US


def test_recorded_idle_splits_into_the_idle_share(recorded):
    run, rec = recorded
    _fill_ring(rec)
    split = P.idle_split(run)
    lo, hi = run.trace.window()
    idle = (hi - lo) - T.busy_ns(run.trace, 0, lo, hi)
    d, l = reduce("dispatch_idle_ms", run), reduce("loop_idle_ms", run)
    assert (d + l) * split["executes"] * 1e6 == pytest.approx(idle)
    assert d > 0 and l > 0
    assert {"put", "sync", "dispatch"} <= set(split["by_span"])
