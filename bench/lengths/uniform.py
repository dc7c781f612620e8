"""Uniform over [min, max]."""
import numpy as np


def quantile(spec, q):
    return spec["min"] + (spec["max"] - spec["min"]) * np.asarray(q)
