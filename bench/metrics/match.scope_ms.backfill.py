"""Watchlist match, scope phase: host time of the program's
``match.scope`` spans (tenant grouping, the queries stacked on the host,
the gallery's tenant code and scope row count) less the device busy time
inside them, per watchlist call (ms)."""
import programspans


def read(view):
    return programspans.phase_ms(view, "match.scope")
