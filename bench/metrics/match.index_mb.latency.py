"""Watchlist match, scan phase: megabytes of host row index sent with the
tenant subsets (the ``index_bytes`` argument of the ``match.scan``
spans), summed per watchlist call."""
import programspans


def read(view):
    b = programspans.arg_per_call(view, "match.scan", "index_bytes")
    return None if b is None else b / 1e6
