"""Open loop, Poisson arrivals at ``rate_per_s``.

``lead_s`` seconds of traffic come before the window opens, so the
window starts in steady state.  The gaps are the exponential law's
stratified quantiles, scaled so that the window holds exactly
``round(rate * seconds)`` arrivals and the lead ``round(rate * lead)``,
and put in the seed's order (``bench.traffic.Order``).
"""
import numpy as np

MODE = "open"


def _times(n: int, span: float, order) -> np.ndarray:
    """``n`` arrivals in [0, span): the first at 0, then the gaps."""
    if n == 0:
        return np.zeros(0)
    q = (np.arange(n) + 0.5) / n
    g = order.permutation(-np.log1p(-q))
    t = np.cumsum(g * (span / g.sum()))
    return np.concatenate([[0.0], t[:-1]])


def offsets(spec, seconds, order):
    rate, lead = spec["rate_per_s"], spec.get("lead_s", 0.0)
    lead_t = _times(round(rate * lead), lead, order) - lead
    win_t = _times(round(rate * seconds), seconds, order)
    return [float(t) for t in np.concatenate([lead_t, win_t])]


def client_settings(spec):
    return {"lead_s": spec.get("lead_s", 0.0)}
