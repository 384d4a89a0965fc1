"""Watchlist match, results phase: entries of the label array the gallery
builds for its lookup (the ``labels`` argument of its ``match.results``
spans), summed per watchlist call."""
import programspans


def read(view):
    return programspans.arg_per_call(view, "match.results", "labels")
