"""Reduce a JAX profiler trace (``.xplane.pb``) to device numbers.

What is read, and from where (one TPU chip's plane is
``/device:TPU:<n>``; host threads are on ``/host:CPU``):

* device operations: line ``XLA Ops``.  Events nest (a ``while`` holds
  the ops of its body); busy time is the union of their intervals, and
  an op's own time is its duration less that of the ops inside it.
* step programs: line ``XLA Modules``, named ``jit_<function>(<id>)``.
* kernels: an op's HLO name before its ``.<n>`` suffix, which for a
  Pallas call is the kernel's name (``%flash_prefill.6`` ->
  ``flash_prefill``).
* host spans: the benchmark's ``TraceAnnotation`` events, named
  ``bench.<what>``; their keyword arguments are the event's stats.

Device and host events share the profile's clock, in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]           # (start_ns, end_ns)

SLACK_NS = 500_000.0
_SUFFIX = re.compile(r"\.\d+$")
_META = re.compile(r"#(.*)#$")


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str                # HLO instruction name, '%' and suffix dropped
    self_ns: float = 0.0


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    meta: Dict[str, str]


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]                  # chip -> ops
    modules: Dict[int, List[Op]]              # chip -> step programs
    spans: List[Span]                         # bench.* host spans

    def window(self) -> Interval:
        """The ``bench.window`` span, else the extent of all events."""
        for s in self.spans:
            if s.name == "bench.window":
                return s.start, s.end
        evs = [o for ops in self.ops.values() for o in ops] + self.spans
        return min(e.start for e in evs), max(e.end for e in evs)


def op_name(hlo: str) -> str:
    """``%flash_prefill.6 = bf16[...] custom-call(...)`` ->
    ``flash_prefill``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(name: str) -> str:
    """``jit__decode_step(1341030120043663034)`` -> ``_decode_step``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _self_times(ops: List[Op]) -> None:
    """Own time of nested events: duration less direct children."""
    stack: List[Op] = []
    for o in ops:
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name) for e in line.events),
                                 key=lambda x: (x[0], -x[1]))
                    ops[chip] = [Op(a, b, op_name(n)) for a, b, n in evs]
                    _self_times(ops[chip])
                elif line.name == "XLA Modules":
                    modules[chip] = sorted(
                        (Op(e.start_ns, e.start_ns + e.duration_ns,
                            module_name(e.name)) for e in line.events),
                        key=lambda o: o.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        sp = _span(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                        sp.meta.update((k, str(v)) for k, v in e.stats)
                        spans.append(sp)
    spans.sort(key=lambda s: s.start)
    return Trace(ops, modules, spans)


def _span(name: str, start: float, end: float) -> Span:
    meta = {}
    m = _META.search(name)
    if m:
        name = name[:m.start()]
        for kv in m.group(1).split(","):
            k, _, v = kv.partition("=")
            meta[k] = v
    return Span(start, end, name, meta)


def clip(ivs: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def union(ivs: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, chip: int, lo: float, hi: float) -> float:
    """Union of the chip's op intervals inside [lo, hi]."""
    ivs = union(clip([(o.start, o.end) for o in tr.ops.get(chip, [])],
                     lo, hi))
    return sum(b - a for a, b in ivs)


def idle_gaps(tr: Trace, chip: int, lo: float, hi: float
              ) -> List[Tuple[str, float, float]]:
    """Each gap of the chip inside [lo, hi] as (host activity, start, ns).
    The activity is whichever covers most of the gap: a ``bench.*`` span
    (``window`` aside), or ``host.other`` for the time no span covers."""
    busy = union(clip([(o.start, o.end) for o in tr.ops.get(chip, [])],
                      lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in tr.spans if s.name != "bench.window"]
    out = []
    for a, b in gaps:
        cover: Dict[str, float] = {}
        for s in spans:
            ov = min(b, s.end) - max(a, s.start)
            if ov > 0:
                cover[s.name] = cover.get(s.name, 0.0) + ov
        covered = union(clip([(s.start, s.end) for s in spans], a, b))
        cover["host.other"] = (b - a) - sum(y - x for x, y in covered)
        out.append((max(cover, key=cover.get), a, b - a))
    return out


def op_seconds(tr: Trace, chip: int, lo: float, hi: float
               ) -> Dict[str, float]:
    """Own device time by op name, ops that start inside [lo, hi]."""
    out: Dict[str, float] = {}
    for o in tr.ops.get(chip, []):
        if lo <= o.start < hi:
            out[o.name] = out.get(o.name, 0.0) + o.self_ns * 1e-9
    return out


def _mid(o: Op) -> float:
    return (o.start + o.end) / 2


def kernel_ns_in(tr: Trace, chip: int, kernel: str, lo: float,
                 hi: float) -> float:
    """Device time of one kernel's calls centred inside [lo, hi]."""
    return sum(o.end - o.start for o in tr.ops.get(chip, [])
               if o.name == kernel and lo <= _mid(o) < hi)


def modules_in(tr: Trace, chip: int, lo: float, hi: float,
               name: Optional[str] = None) -> List[Op]:
    """Step programs centred inside [lo, hi].  The device's clock is
    aligned to the host's only to a fraction of a millisecond, so a
    program may seem to start before the host call that launched it:
    callers widen a host span by ``SLACK_NS``."""
    return [m for m in tr.modules.get(chip, [])
            if lo <= _mid(m) < hi and (name is None or m.name == name)]
