"""Device numbers of a traced run: busy and window seconds, the steps
whose device work the trace holds, and the ``breakdown`` of the result
line (top device ops; longest idle gaps by what the host was doing)."""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

from bench import trace as T


def busy(run) -> Dict[str, float]:
    """``busy_s`` averaged over the chips used, and ``window_s``."""
    lo, hi = run.trace.window()
    b = sum(T.busy_ns(run.trace, c, lo, hi) for c in range(run.chips))
    return {"busy_s": b / run.chips * 1e-9, "window_s": (hi - lo) * 1e-9}


def traced_steps(run) -> Iterator[Tuple[object, T.Span]]:
    """(step record, its ``bench.execute`` span) for each step that ran
    wholly inside the traced window."""
    lo, hi = run.trace.window()
    by_idx = {s.idx: s for s in run.steps}
    for sp in run.trace.spans:
        if sp.name == "bench.execute" and sp.start >= lo and sp.end <= hi:
            step = by_idx.get(int(sp.meta.get("step", -1)))
            if step is not None:
                yield step, sp


def bounds(span) -> Tuple[float, float]:
    """A host span widened by the clocks' misalignment."""
    return span.start - T.SLACK_NS, span.end + T.SLACK_NS


def program_ns(run, step, span, name: str) -> float:
    """Device time of the step's programs called ``name``."""
    return sum(m.end - m.start for m in
               T.modules_in(run.trace, step.chip, *bounds(span), name))


def kernel_ns(run, step, span, kernel: str) -> float:
    return T.kernel_ns_in(run.trace, step.chip, kernel, *bounds(span))


def breakdown(run) -> Dict[str, List]:
    lo, hi = run.trace.window()
    ops: Dict[str, float] = {}
    gaps = []
    for c in range(run.chips):
        for k, v in T.op_seconds(run.trace, c, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v / run.chips
        gaps += [(f"{label}@chip{c}", ns * 1e-9)
                 for label, _, ns in T.idle_gaps(run.trace, c, lo, hi)]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}
