"""Median `decode.dispatch.call` in the window: the calls that enqueue (an
assembly of the ids where the batch changed, and the decode step's own):
JAX's dispatch path, argument by argument, as the loop's thread lives it."""
from benchmarks.harness import loop_records


def read(rec):
    return loop_records.median_ms(rec, "decode.dispatch.call")
