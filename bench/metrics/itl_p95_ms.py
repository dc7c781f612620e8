"""95th percentile of every gap between two streamed tokens of one
request whose later token arrived in the window."""
from bench.harness import percentile


def reduce(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r["tokens"], r["tokens"][1:])
            if run.in_window(b)]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
