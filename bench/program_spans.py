"""The program's own spans (``repro.obs``), as the per-layer metrics
that name them read them, and their place on a profile's clock.

The program records its spans on ``time.monotonic_ns()`` in one ring
per process, which the metrics read after the run.  A profile has a
clock of its own.  One offset per run maps the first onto the second:
the median, over the steps the profile holds, of the ``bench.execute``
annotation's start less that step's ``Step.t0`` (the wrapper reads
``t0`` just before it enters the annotation).  The ``rapid.*``
annotations the program leaves in the profile are a cross-check of the
mapping (``alignment``), not a second source.

A program without ``repro.obs`` records nothing: every reader returns
None then, as it does when the ring dropped spans of the window.
"""
from __future__ import annotations

import statistics
import sys
from typing import Dict, List, Optional

from bench import trace as T

# the innermost span around an idle gap, as section 5 of PERF.md names it
LABELS = {"rapid.put": "put", "rapid.sync": "sync",
          "rapid.insert": "insert", "rapid.prefill": "dispatch",
          "rapid.decode": "dispatch", "rapid.execute": "execute",
          "rapid.schedule": "schedule", "rapid.step": "step",
          "rapid.emit": "emit", "rapid.submit": "submit"}


def recorder():
    """``repro.obs``, or None for a program that has no recorder."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def spans(run, name: str) -> Optional[List]:
    """The ``name`` spans that start at or after the start of the run's
    host span, oldest first; None without a recorder, or when the ring
    dropped a span that ended after that start."""
    obs = recorder()
    if obs is None:
        return None
    lo = int(run.host_span()[0] * 1e9)
    if not obs.complete_since(lo):
        return None
    return [s for s in obs.spans() if s.name == name and s.start >= lo]


def in_host_span(run, name: str) -> Optional[List]:
    """The ``name`` spans that start inside the run's host span."""
    got = spans(run, name)
    if got is None:
        return None
    hi = run.host_span()[1] * 1e9
    return [s for s in got if s.start < hi]


def share(spans_, part, whole) -> Optional[float]:
    """sum(part) / sum(whole) over the spans' attributes, in %."""
    if not spans_:
        return None
    w = sum(whole(s.attrs) for s in spans_)
    return sum(part(s.attrs) for s in spans_) / w * 100 if w else None


# -- the profile's clock -------------------------------------------------------

def offsets(run) -> List[float]:
    """Per traced step: ``bench.execute``'s start in the profile less
    the step's ``Step.t0``, in ns."""
    by_idx = {s.idx: s for s in run.steps}
    out = []
    for sp in run.trace.spans:
        if sp.name == "bench.execute":
            step = by_idx.get(int(sp.meta.get("step", -1)))
            if step is not None:
                out.append(sp.start - step.t0 * 1e9)
    return out


def idle_split(run) -> Optional[Dict]:
    """The device's idle time over the traced slice, each piece put
    under the innermost program span around it on the profile's clock
    (``none`` where no span is open), summed over the chips:
    ``by_span`` (label -> ns), ``inside`` and ``outside`` the
    ``rapid.execute`` spans (ns), ``executes`` (the ``rapid.execute``
    spans that start in the slice) and ``offset_spread_ns``.  None in an
    untraced run or without program spans."""
    if run.trace is None:
        return None
    cached = getattr(run, "_program_idle", None)
    if cached is not None:
        return cached
    obs, offs = recorder(), offsets(run)
    if obs is None or not offs:
        return None
    off = statistics.median(offs)
    lo, hi = run.trace.window()
    if not obs.complete_since(int(lo - off)):
        return None
    mapped = [(s.start + off, s.end + off, s) for s in obs.spans()
              if s.name in LABELS and s.end + off > lo and s.start + off < hi]
    out = {"by_span": {}, "inside": 0.0, "outside": 0.0, "executes": 0,
           "offset_spread_ns": max(offs) - min(offs)}
    for chip in range(run.chips):
        mine = [m for m in mapped if m[2].attrs.get("worker") == chip]
        out["executes"] += sum(1 for a, _, s in mine
                               if s.name == "rapid.execute" and lo <= a < hi)
        for _, start, ns in T.idle_gaps(run.trace, chip, lo, hi):
            _attribute(start, start + ns, mine, out)
    run._program_idle = out
    _log(run, out)
    return out


def _attribute(a: float, b: float, mapped, out: Dict) -> None:
    """Split the gap [a, b) at the span edges inside it; each piece goes
    to the innermost span that covers it."""
    over = [m for m in mapped if m[0] < b and m[1] > a]
    cuts = sorted({a, b} | {t for m in over for t in m[:2] if a < t < b})
    for x, y in zip(cuts, cuts[1:]):
        cover = [m for m in over if m[0] <= x and m[1] >= y]
        label, inside = "none", False
        if cover:
            inner = max(cover, key=lambda m: (m[0], -m[1]))
            label = LABELS[inner[2].name]
            inside = any(m[2].name == "rapid.execute" for m in cover)
        out["by_span"][label] = out["by_span"].get(label, 0.0) + (y - x)
        out["inside" if inside else "outside"] += y - x


def _log(run, split: Dict) -> None:
    """One line on standard error for whoever reads the run's log."""
    lo, hi = run.trace.window()
    idle = sum(split["by_span"].values())
    check = alignment(run)
    by = ", ".join(f"{k} {v * 1e-6:.3f} ms" for k, v in
                   sorted(split["by_span"].items(), key=lambda kv: -kv[1]))
    w0, w1 = (t * 1e9 for t in run.window)
    compiles = [s.attrs.get("compiles", 0) for s in recorder().spans()
                if s.name == "rapid.step" and w0 <= s.start < w1]
    print(f"program spans: clock offset spread "
          f"{split['offset_spread_ns'] * 1e-3:.1f} us; "
          f"{check if check else 'no rapid.* annotations to check'}; "
          f"{split['executes']} rapid.execute, idle {idle * 1e-9:.6f} s of "
          f"{(hi - lo) * 1e-9 * run.chips:.6f} s ({by}); compiles in the "
          f"window {max(compiles, default=0) - min(compiles, default=0)}",
          file=sys.stderr, flush=True)


# -- the cross-check ---------------------------------------------------------------

def profile_spans(path: str) -> List[T.Span]:
    """The ``rapid.*`` annotations of a profile, on its clock."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rapid."):
                    out.append(T.Span(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name,
                                      {k: str(v) for k, v in e.stats}))
    return sorted(out, key=lambda s: s.start)


def twin_errors(recorded, annotated, offset: float) -> List[float]:
    """|mapped start - profile start| in ns of each ``rapid.execute``
    the ring and the profile both hold, matched by worker and step."""
    seen = {(s.meta.get("worker"), s.meta.get("step")): s.start
            for s in annotated if s.name == "rapid.execute"}
    out = []
    for s in recorded:
        if s.name == "rapid.execute":
            key = (str(s.attrs.get("worker")), str(s.attrs.get("step")))
            if key in seen:
                out.append(abs(s.start + offset - seen[key]))
    return out


def alignment(run) -> Optional[str]:
    """The mapping checked against the run's profile, in words."""
    from bench.harness import ROOT
    found = sorted((ROOT / ".bench_trace" / run.cell.get("name", "")
                    ).rglob("*.xplane.pb")) if run.cell else []
    obs, offs = recorder(), offsets(run)
    if not found or obs is None or not offs:
        return None
    errs = twin_errors(obs.spans(), profile_spans(str(found[-1])),
                       statistics.median(offs))
    if not errs:
        return None
    return (f"{len(errs)} rapid.execute within {max(errs) * 1e-3:.1f} us "
            f"of their profile twins")
