"""Traffic from a mix file and a seed.

A mix (``bench/traffic/<mix>.json``) names an arrival process and a
length law for prompts and for outputs; each is a module found by that
name under ``bench/arrivals/`` or ``bench/lengths/``.  A new process or
law is a new module there, and a new mix is a new data file.

Every seed gets the same work in another order: the lengths are the
law's stratified quantiles, the gaps of an open loop the exponential's,
and the seed only orders them (``Order``).  The order is drawn within
blocks of the mix's ``block`` consecutive requests, each of which holds
one value from each of ``block`` strata of the law, so that no seed
bunches long prompts or short gaps: runs on different seeds differ in
order and not in load, over any stretch of ``block`` requests.
"""
from __future__ import annotations

import importlib
from typing import Dict

import numpy as np


def _package_module(kind: str, name: str):
    return importlib.import_module(f"bench.{kind}.{name}")


class Order:
    """One seed's order of ``n`` sorted values: block ``k`` takes the
    ``k``-th member (in a seeded shuffle) of each of ``block`` strata of
    consecutive values, then is shuffled itself.  ``block`` None is a
    plain shuffle of all ``n``."""

    def __init__(self, rng, block=None):
        self.rng = rng
        self.block = block

    def indices(self, n: int) -> np.ndarray:
        if not self.block or self.block >= n:
            return self.rng.permutation(n)
        strata = [self.rng.permutation(s)
                  for s in np.array_split(np.arange(n), self.block)]
        out = []
        for k in range(len(strata[0])):
            blk = np.array([s[k] for s in strata if k < len(s)])
            out.append(self.rng.permutation(blk))
        return np.concatenate(out)

    def permutation(self, vals) -> np.ndarray:
        """``vals`` sorted, then put in this seed's order."""
        vals = np.sort(np.asarray(vals))
        return vals[self.indices(len(vals))]


def lengths(law: Dict, n: int, module=_package_module) -> np.ndarray:
    """``n`` lengths: the law's stratified quantiles, ascending."""
    q = (np.arange(n) + 0.5) / n
    vals = module("lengths", law["law"]).quantile(law, q)
    return np.clip(np.rint(vals), law["min"], law["max"]).astype(np.int64)


def build(mix: Dict, seed: int, seconds: float,
          module=_package_module) -> Dict:
    """The client's plan: ``requests`` as ``[offset_s, prompt, output]``
    (offsets from the window's start; ``None`` in a closed loop) and the
    arrival settings the client needs."""
    arr = mix["arrival"]
    block = mix.get("block")
    proc = module("arrivals", arr["process"])
    offsets = proc.offsets(
        arr, seconds, Order(np.random.default_rng([seed, 0x7A1]), block))
    n = len(offsets)
    # prompts and outputs are paired the same way for every seed, and
    # the pairs are ordered by prompt, then output, for the seed to order
    prompts = lengths(mix["prompt"], n, module)
    outputs = np.random.default_rng(0x5EED).permutation(
        lengths(mix["output"], n, module))
    by = np.lexsort((outputs, prompts))
    pairs = np.stack([prompts[by], outputs[by]], 1)
    pairs = pairs[Order(np.random.default_rng([seed, 1]), block).indices(n)]
    reqs = [[None if o is None else float(o), int(p), int(g)]
            for o, (p, g) in zip(offsets, pairs)]
    return {"mode": proc.MODE, "requests": reqs, "seconds": seconds,
            **proc.client_settings(arr)}
