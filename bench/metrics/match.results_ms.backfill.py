"""Watchlist match, results phase: host time of the program's
``match.results`` spans (match stats, the label array and lookup, scores
and result messages back to the caller) less the device busy time inside
them, per watchlist call (ms)."""
import programspans


def read(view):
    return programspans.phase_ms(view, "match.results")
