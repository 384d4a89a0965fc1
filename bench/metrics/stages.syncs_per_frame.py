"""Stage payloads: the program's ``cartridge.sync`` spans (a stage call's
wait for its result) in the window, counted and divided by the frames
served; one per frame per stage while each call is waited on alone."""
import programspans


def read(view):
    return programspans.syncs_per_frame(view)
