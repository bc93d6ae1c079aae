"""Of the seconds by which the window's long turns exceed their limit
(`loop_long_turn_share`), the share a `host.hiccups` row's
`[t, t + late_s]` overlaps: no Python thread of the process ran, so the
pause was the host's (the machine, the hypervisor, a collector, a C call
that kept the interpreter lock). 0 where the window held no long turn
(`loop_long_turn_share` reads 0 beside it: none of no seconds were the
host's), so that a traced line of a program that records its turn from the
inside always holds it; None where the program does not."""
from benchmarks.harness import loop_records


def read(rec):
    return loop_records.pause_share(rec, "host_s")
