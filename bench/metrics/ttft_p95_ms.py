"""95th percentile of time to first token over every request due in the
window, timed from when it was due.  A request that got no token counts
with the time until its stream ended."""
from bench.harness import percentile


def reduce(run):
    vals = [(r["tokens"][0] if r["tokens"] else r["end_t"]) - r["due"]
            for r in run.due_in_window()]
    p = percentile([v for v in vals if v is not None], 95)
    return None if p is None else p * 1e3
