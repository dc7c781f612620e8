"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5 lite (``testdata/small.xplane.pb``: the registry's
reduced starcoder2-3b, three prefills of 128 tokens and three decode
steps of 8 slots, each inside a ``bench.execute`` annotation, 10 ms of
host sleep after each)."""
import pathlib

import pytest

from bench import trace as T

FIXTURE = pathlib.Path(__file__).parent / "testdata" / "small.xplane.pb"


def _tr(ops, spans=(), modules=()):
    tr = T.Trace({0: [T.Op(a, b, n) for a, b, n in ops]},
                 {0: [T.Op(a, b, n) for a, b, n in modules]},
                 [T.Span(a, b, n, {}) for a, b, n in spans])
    T._self_times(tr.ops[0])
    return tr


def test_union_and_clip_by_hand():
    ivs = [(0, 10), (5, 20), (30, 40), (39, 41), (50, 60)]
    assert T.union(ivs) == [(0, 20), (30, 41), (50, 60)]
    assert T.clip(ivs, 8, 55) == [(8, 10), (8, 20), (30, 40), (39, 41),
                                  (50, 55)]


def test_busy_is_the_union_of_nested_ops():
    # a while loop [0, 100) holding two ops, then a lone op [150, 160)
    tr = _tr([(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (150, 160, "c")])
    assert T.busy_ns(tr, 0, 0, 200) == 110
    assert T.busy_ns(tr, 0, 50, 155) == 55
    own = T.op_seconds(tr, 0, 0, 200)
    assert own["while"] == pytest.approx(30e-9)
    assert own["b"] == pytest.approx(50e-9)


def test_gaps_take_the_host_span_that_covers_most():
    tr = _tr([(0, 10, "a"), (40, 50, "b"), (100, 110, "c")],
             spans=[(5, 35, "bench.execute"), (60, 100, "bench.submit"),
                    (0, 200, "bench.window")])
    gaps = T.idle_gaps(tr, 0, 0, 120)
    assert gaps == [("bench.execute", 10, 30), ("bench.submit", 50, 50),
                    ("host.other", 110, 10)]


def test_span_metadata_is_parsed():
    s = T._span("bench.execute#step=12,chip=3#", 1.0, 2.0)
    assert (s.name, s.meta) == ("bench.execute", {"step": "12", "chip": "3"})


def test_names_of_kernels_and_programs():
    assert T.op_name("%flash_prefill.6 = bf16[1,24] custom-call(x)") == \
        "flash_prefill"
    assert T.op_name("%copy-start.12 = (s32[1,128]) copy-start(%t)") == \
        "copy-start"
    assert T.module_name("jit__decode_step(1341030120043663034)") == \
        "_decode_step"


@pytest.fixture(scope="module")
def small():
    return T.load(str(FIXTURE))


def test_recorded_trace_programs_and_kernels(small):
    names = [m.name for m in small.modules[0]]
    assert names == ["_prefill_step", "_decode_step"] * 3
    lo, hi = small.window()
    # summed from the recorded events: 3 kernel calls of each
    assert T.kernel_ns_in(small, 0, "flash_prefill", lo, hi) == 28062.0
    assert T.kernel_ns_in(small, 0, "paged_attention", lo, hi) == 65031.0
    assert len(T.modules_in(small, 0, lo, hi, "_decode_step")) == 3


def test_recorded_trace_busy_and_idle(small):
    lo, hi = small.window()
    busy = T.busy_ns(small, 0, lo, hi)
    programs = sum(m.end - m.start for m in small.modules[0])
    # every op runs inside a program: busy is at most the programs' time,
    # and at least the kernels' time
    assert 28062.0 + 65031.0 < busy <= programs
    gaps = T.idle_gaps(small, 0, lo, hi)
    assert sum(g for _, _, g in gaps) == pytest.approx(hi - lo - busy)


def test_recorded_trace_gaps_are_attributed(small):
    lo, hi = small.window()
    gaps = T.idle_gaps(small, 0, lo, hi)
    labels = {label for label, _, _ in gaps}
    assert labels == {"bench.execute", "host.other"}
    # the five 10 ms sleeps between the six annotated calls are the
    # longest gaps, and no host span covers them
    longest = sorted(gaps, key=lambda g: -g[2])[:5]
    assert all(label == "host.other" and ns > 10e6
               for label, _, ns in longest)
    # the host's part of each call (dispatch, transfers, the wait for
    # the result) is attributed to the call's span
    inside = [g for g in gaps if g[0] == "bench.execute"]
    assert len(inside) >= 6
