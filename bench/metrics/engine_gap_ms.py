"""Host time between one replica's ``execute`` calls while it had
requests decoding (the next call decodes one that the last did), summed
over the window and divided by the steps in it."""


def reduce(run):
    steps = run.steps_in_host_span()
    if not steps:
        return None
    last = {}
    gap = 0.0
    for s in sorted(steps, key=lambda s: s.t0):
        prev = last.get(s.chip)
        if prev is not None and set(prev.decode_rids) & set(s.decode_rids):
            gap += s.t0 - prev.t1
        last[s.chip] = s
    return gap / len(steps) * 1e3
