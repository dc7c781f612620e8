"""Median time to first token over every request due in the window,
timed from when it was due.  A request that got no token counts with
the time until its stream ended."""
from bench.harness import percentile


def ttfts(run):
    """Seconds to first token of each request due in the window."""
    vals = [(r["tokens"][0] if r["tokens"] else r["end_t"]) - r["due"]
            for r in run.due_in_window()]
    return [v for v in vals if v is not None]


def reduce(run):
    p = percentile(ttfts(run), 50)
    return None if p is None else p * 1e3
