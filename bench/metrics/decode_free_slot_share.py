"""Share of the slot-steps the decode steps of the window computed that
held no request: free over computed slot-steps of the ``rapid.decode``
spans (``slots - live`` over ``slots``)."""
from bench import program_spans as P


def reduce(run):
    return P.share(P.in_host_span(run, "rapid.decode"),
                   lambda a: a["slots"] - a["live"], lambda a: a["slots"])
