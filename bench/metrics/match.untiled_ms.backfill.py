"""Watchlist match, outside the phases: host time of the program's
``match.batch`` spans less that of the four phases inside them (the
grouping loop's own lines), per watchlist call (ms)."""
import programspans


def read(view):
    return programspans.untiled_ms(view)
