"""Watchlist match: mean over the watchlist stage's calls of the span less
the device busy time inside it, the host's own time per call (ms)."""


def read(view):
    return view.match_host_ms()
