"""Of the seconds by which the window's long turns exceed their limit
(`loop_long_turn_share`), the share inside a `.wait` span, beyond the
median turn's wait, and under NO hiccup: the process was running and the
device or its runtime was late. 0 where the window held no long turn, as
`pause_host_share`. What is left of 1 after this and `pause_host_share`,
where `loop_long_turn_share` is over 0, is the loop's thread alone losing
the processor."""
from benchmarks.harness import loop_records


def read(rec):
    return loop_records.pause_share(rec, "device_s")
