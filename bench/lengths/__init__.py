"""Length laws, one module each, found by the name a mix gives.  A
module defines ``quantile(spec, q)`` -> the law's values at the
probabilities ``q`` (an array), before the mix's clip to [min, max]."""
