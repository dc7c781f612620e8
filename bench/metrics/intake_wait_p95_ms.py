"""95th percentile, over requests due in the window (in a traced run,
before the profiler started), of the time from when the request was due
to the start of its ``rapid.submit`` span: the wait before the gateway
reads it, while the event loop is busy elsewhere."""
from bench import program_spans as P
from bench.harness import percentile


def reduce(run):
    subs = P.spans(run, "rapid.submit")
    if subs is None:
        return None
    start = {}
    for s in subs:
        start.setdefault(s.attrs.get("rid"), s.start * 1e-9)
    vals = [start[r["rid"]] - r["due"] for r in run.due_in_host_span()
            if r["rid"] in start]
    p = percentile(vals, 95)
    return None if p is None else p * 1e3
