"""Scan: the least time of the 1:N searches the window needed, at the
chip's bf16 MXU peak and HBM bandwidth, over the device busy time inside
the watchlist stage's spans (%)."""


def read(view):
    return view.match_roofline()
