"""Lognormal with ``median`` and log-space ``sigma``, clipped by the mix.
The quantiles are taken within the clip, so none pile up at its ends."""
import math

import numpy as np
from statistics import NormalDist


def quantile(spec, q):
    mu, s = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    lo = nd.cdf((math.log(spec["min"]) - mu) / s)
    hi = nd.cdf((math.log(spec["max"]) - mu) / s)
    z = [nd.inv_cdf(lo + (hi - lo) * float(p)) for p in q]
    return np.exp(mu + s * np.asarray(z))
