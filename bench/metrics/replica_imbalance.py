"""Most requests routed to one replica over the mean, for the requests
due in the window (the gateway's assignment records)."""


def reduce(run):
    if run.chips < 2:
        return None
    n = [0] * run.chips
    for r in run.due_in_host_span():
        rep = run.replica_of.get(r["rid"])
        if rep is not None:
            n[rep] += 1
    mean = sum(n) / run.chips
    return max(n) / mean if mean else None
