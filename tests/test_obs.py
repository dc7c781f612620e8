"""The span and counter recorder (``repro.obs``) and what the served
path records through it."""
import statistics
import time

import pytest

from repro import obs
from repro.config import ServeConfig, get_config
from repro.core import Request
from repro.serving import EventLoop, Gateway


@pytest.fixture(autouse=True)
def empty_recorder():
    obs.reset()
    yield
    obs.reset()


def test_spans_nest_and_name_their_parents():
    with obs.span("rapid.step", worker=1) as outer:
        with obs.span("rapid.schedule", worker=1) as mid:
            with obs.span("rapid.put", worker=1):
                pass
            mid.set(admits=2)
        with obs.span("rapid.execute", worker=1, step=7):
            pass
    got = {s.name: s for s in obs.spans()}
    assert list(got) == ["rapid.put", "rapid.schedule", "rapid.execute",
                         "rapid.step"]          # kept as they end
    assert got["rapid.step"].parent == 0
    assert got["rapid.schedule"].parent == outer.sid
    assert got["rapid.execute"].parent == outer.sid
    assert got["rapid.put"].parent == mid.sid
    assert got["rapid.schedule"].attrs == {"worker": 1, "admits": 2}
    assert got["rapid.execute"].attrs == {"worker": 1, "step": 7}
    for s in got.values():
        assert 0 < s.start <= s.end
    assert got["rapid.step"].start <= got["rapid.put"].start
    assert got["rapid.put"].end <= got["rapid.step"].end
    assert len({s.sid for s in got.values()}) == 4


def test_a_span_that_raises_is_kept_and_unwinds():
    with pytest.raises(ValueError):
        with obs.span("rapid.step", worker=0):
            raise ValueError("boom")
    with obs.span("rapid.emit", worker=0):
        pass
    step, emit = obs.spans()
    assert step.name == "rapid.step" and emit.parent == 0


def test_record_keeps_a_span_begun_earlier():
    t0 = time.monotonic_ns()
    with obs.span("rapid.prefill", rid=4) as sp:
        rec = obs.record("rapid.queue", t0 - 5_000_000, sp.start, rid=4,
                         preempted=0, worker=0)
    queue, prefill = obs.spans()
    assert queue is rec and queue.name == "rapid.queue"
    assert queue.start == t0 - 5_000_000 and queue.end == prefill.start
    assert queue.parent == 0 and queue.sid != prefill.sid


def test_the_ring_is_bounded_and_readers_see_what_it_dropped(monkeypatch):
    import collections
    monkeypatch.setattr(obs, "RING", 4)
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=4))
    for i in range(3):
        obs.record("rapid.queue", 100 * i, 100 * i + 50, rid=i)
    assert obs.complete_since(0)
    for i in range(3, 6):
        obs.record("rapid.queue", 100 * i, 100 * i + 50, rid=i)
    kept = obs.spans()
    assert [s.attrs["rid"] for s in kept] == [2, 3, 4, 5]
    # spans 0 and 1 (ended at 50 and 150) are gone: a window from 150 on
    # is not whole, one from 151 on is
    assert not obs.complete_since(150)
    assert obs.complete_since(151)
    obs.reset()
    assert obs.spans() == [] and obs.complete_since(0)


def test_counters_add_and_snapshot():
    assert set(obs.COUNTERS) <= set(obs.counters())
    assert all(v == 0 for v in obs.counters().values())
    obs.count("steps")
    obs.count("prefill_tokens_valid", 40)
    obs.count("prefill_tokens_valid", 2)
    snap = obs.counters()
    obs.count("steps")
    assert snap["steps"] == 1 and obs.counter("steps") == 2
    assert snap["prefill_tokens_valid"] == 42
    obs.reset()
    assert obs.counter("steps") == 0


def test_compiles_are_counted():
    import jax
    import jax.numpy as jnp
    c = float(time.monotonic_ns() % 1_000_003)    # a program never cached
    f = jax.jit(lambda x: x * c + 1)
    x, y = jnp.arange(5.0), jnp.ones(5)
    before = obs.counter("compiles")
    f(x).block_until_ready()
    compiled = obs.counter("compiles")
    f(y).block_until_ready()                        # same shape: cached
    assert compiled > before and obs.counter("compiles") == compiled


def test_a_span_with_no_profiler_is_cheap():
    per = []
    for _ in range(10_000):
        t = time.perf_counter_ns()
        with obs.span("rapid.put", worker=0):
            pass
        per.append(time.perf_counter_ns() - t)
    assert statistics.median(per) < 20_000        # ns


def _trace(n):
    return [Request(rid=i, arrival=0.05 * i, prompt_len=200 + 10 * i,
                    max_new_tokens=6) for i in range(n)]


def test_served_path_records_engine_spans_and_metrics_carry_counters():
    cfg = get_config("llama3-70b")
    serve = ServeConfig(mode="rapid", chips=32)
    gw = Gateway(cfg, serve, modes=("rapid", "rapid"), clock=EventLoop())
    gw.serve_trace(_trace(6))
    names = {s.name for s in obs.spans()}
    assert {"rapid.submit", "rapid.step", "rapid.schedule",
            "rapid.emit"} <= names
    subs = [s for s in obs.spans() if s.name == "rapid.submit"]
    assert sorted(s.attrs["rid"] for s in subs) == list(range(6))
    assert {s.attrs["worker"] for s in subs} <= {0, 1}
    steps = [s for s in obs.spans() if s.name == "rapid.step"]
    assert all({"worker", "wake", "step", "compiles"} <= set(s.attrs)
               for s in steps)
    emitted = sum(s.attrs["tokens"] for s in obs.spans()
                  if s.name == "rapid.emit")
    assert emitted == 6 * 6
    summary = gw.metrics_summary()
    assert summary["counters"] == obs.counters()
    assert set(obs.COUNTERS) <= set(summary["counters"])


def test_kv_utilization_is_a_running_mean():
    from repro.core import drive, make_engine
    cfg = get_config("llama3-70b")
    eng = make_engine("rapid", cfg, ServeConfig(mode="rapid", chips=32))
    assert eng.kv_util_mean == 0.0
    seen = []
    inner = eng._note_kv_util

    def note():
        seen.append(eng.kv.utilization)
        inner()
    eng._note_kv_util = note
    drive(eng, _trace(4))
    assert eng.kv_util_samples == len(seen) > 0
    assert eng.kv_util_mean == pytest.approx(sum(seen) / len(seen),
                                             rel=1e-12)
