"""Share of the window the engine loop's thread spent ON the processor
inside its turns: the sum of `cpu_s` (`time.thread_time()` at a turn's
edges) over `decode.turn`."""
from benchmarks.harness import loop_records


def read(rec):
    return loop_records.fact_share(rec, ("decode.turn",), "cpu_s")
