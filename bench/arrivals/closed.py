"""Closed loop: ``clients`` callers, each sending its next request when
the last one ends.  The window opens once ``fill`` requests are being
served at once (every slot busy).  The pool holds enough requests for
every client to send one each ``pool_every_s`` seconds of the window.
"""
import math

MODE = "closed"


def offsets(spec, seconds, order):
    per_client = 2 + math.ceil(seconds / spec.get("pool_every_s", 2.0))
    return [None] * (spec["clients"] * per_client)


def client_settings(spec):
    return {"clients": spec["clients"], "fill": spec["fill"]}
