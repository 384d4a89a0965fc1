"""Stage payloads: time in the program's ``cartridge.sync`` spans (the
host waiting for each stage call's result) per frame served (ms)."""
import programspans


def read(view):
    return programspans.stage_ms_per_frame(view, programspans.SYNC)
