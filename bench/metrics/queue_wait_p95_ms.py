"""95th percentile, over requests due in the window (in a traced run,
before the profiler started), of the time from when the request was due
to the start of the ``execute`` call that prefilled it (the benchmark's
wrapper around ``execute``)."""
from bench.harness import percentile


def reduce(run):
    start = {}
    for s in run.steps:
        for rid, _ in s.prefill:
            start.setdefault(rid, s.t0)
    vals = [start[r["rid"]] - r["due"] for r in run.due_in_host_span()
            if r["rid"] in start]
    p = percentile(vals, 95)
    return None if p is None else p * 1e3
