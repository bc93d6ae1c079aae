"""Share of the window by which turns exceed 50 ms + 3 x the window's median
turn (`loop_records.LONG_S`, `LONG_MEDIANS`): what pauses cost as the engine
sees them. Reported with `pause_host_share` / `pause_device_share`, which
say whose they were, where the program records its turn from the inside."""
from benchmarks.harness import loop_records


def read(rec):
    pauses = loop_records.pauses(rec)
    if pauses is None:
        return None
    return pauses["excess_s"] / pauses["window_s"]
