"""Stage payloads: harness span time (host clock, read from the trace) of
the detector, quality and embedder ``process_batch`` calls, summed and
divided by the frames served (ms).  The frames are already on the device
(their transfer is the ``lane.h2d`` span), so the profiler's cost on
host-to-device copies stays out of it."""


def read(view):
    return view.stage_ms_per_frame()
