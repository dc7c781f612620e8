"""Device idle time outside every ``rapid.execute`` span (the event
loop, schedule, emit, HTTP), over the traced slice, per
``rapid.execute`` in it; with ``dispatch_idle_ms`` it sums to the idle
time ``idle_share`` reads."""
from bench import program_spans as P


def reduce(run):
    split = P.idle_split(run)
    if not split or not split["executes"]:
        return None
    return split["outside"] / split["executes"] * 1e-6
