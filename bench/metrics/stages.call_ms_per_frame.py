"""Stage payloads: time in the program's ``cartridge.call`` spans (the
host dispatching each stage call, before it waits) per frame served
(ms)."""
import programspans


def read(view):
    return programspans.stage_ms_per_frame(view, programspans.CALL)
