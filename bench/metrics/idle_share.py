"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""
from bench.breakdown import busy


def reduce(run):
    if run.trace is None:
        return None
    b = busy(run)
    if b["window_s"] <= 0:
        return None
    return (1 - b["busy_s"] / b["window_s"]) * 100
