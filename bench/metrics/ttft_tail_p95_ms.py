"""95th percentile of time to first token over every request due in the
window, timed from when it was due (``ttft_p50_ms``'s times).

A per-layer reading of the queue's tail: a whole-machine stall of a
second or more, which some runs meet, delays enough of a 51 s window's
requests to set this percentile, so ``ttft_p50_ms`` is the end-to-end
reading."""
from bench.harness import percentile
from bench.metrics.ttft_p50_ms import ttfts


def reduce(run):
    p = percentile(ttfts(run), 95)
    return None if p is None else p * 1e3
