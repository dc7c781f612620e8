"""Mean decode step: the decode ``LaunchOutcome`` duration less the
prefill launched before it in the same call (both count from the
call's start), over the window's decode steps."""


def reduce(run):
    ts = [s.d_decode - (s.d_prefill or 0.0) for s in run.steps_in_host_span()
          if s.d_decode is not None]
    return sum(ts) / len(ts) * 1e3 if ts else None
