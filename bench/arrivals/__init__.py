"""Arrival processes, one module each, found by the name a mix gives.

A module defines ``MODE`` (``open`` or ``closed``), ``offsets(spec,
seconds, order)`` -> one entry per request (seconds from the window's
start, or ``None`` where the client decides when), where
``order.permutation(values)`` puts values in the seed's order
(``bench.traffic.Order``), and
``client_settings(spec)`` -> extra keys for the client.
"""
