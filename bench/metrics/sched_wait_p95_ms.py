"""95th percentile of the ``rapid.queue`` spans that start in the
window (in a traced run, before the profiler started): from a request's
entry into its engine's queue to the start of its prefill."""
from bench import program_spans as P
from bench.harness import percentile


def reduce(run):
    qs = P.in_host_span(run, "rapid.queue")
    if qs is None:
        return None
    p = percentile([s.end - s.start for s in qs], 95)
    return None if p is None else p * 1e-6
