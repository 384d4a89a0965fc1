"""Watchlist match, protect phase: host time of the program's
``match.protect`` spans (the queries' keyed rotation, its QR included)
less the device busy time inside them, per watchlist call (ms)."""
import programspans


def read(view):
    return programspans.phase_ms(view, "match.protect")
