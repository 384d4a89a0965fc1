"""Watchlist match, scan phase: host time of the program's ``match.scan``
spans (per shard: prepared view, tenant-row subset, kernel call, indices
to the host; the ANN coarse scan and a cross-shard merge) less the device
busy time inside them, per watchlist call (ms)."""
import programspans


def read(view):
    return programspans.phase_ms(view, "match.scan")
