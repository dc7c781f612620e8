"""Tokens delivered in the window (of every request, in flight ones
too), over the window's seconds."""


def reduce(run):
    n = sum(1 for r in run.requests for t in r["tokens"] if run.in_window(t))
    return n / (run.window[1] - run.window[0])
