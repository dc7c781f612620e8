"""Prefill time per thousand valid prompt tokens: the prefill
``LaunchOutcome`` durations of the window's steps over their tokens."""


def reduce(run):
    t = n = 0
    for s in run.steps_in_host_span():
        if s.d_prefill is not None and s.prefill:
            t += s.d_prefill
            n += sum(k for _, k in s.prefill)
    return t / n * 1e6 if n else None
