"""Share of the prompt tokens the prefills of the window computed that
are padding up to the bucket: padded over computed tokens of the
``rapid.prefill`` spans (``bucket - valid`` over ``bucket``)."""
from bench import program_spans as P


def reduce(run):
    return P.share(P.in_host_span(run, "rapid.prefill"),
                   lambda a: a["bucket"] - a["valid"], lambda a: a["bucket"])
