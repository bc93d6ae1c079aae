"""Share of the window for which the HOST saw the device's queue empty: the
sum of `starved_s` over `decode.dispatch` and `decode.prefill` (from the
probe that found the newest program in flight done, to the return of the
span's first enqueuing call). A lower bound on the device's idle share that
needs no profiler, so the whole recorded window has one, beside the traced
seconds' `device_idle_share`."""
from benchmarks.harness import loop_records


def read(rec):
    return loop_records.fact_share(
        rec, ("decode.dispatch", "decode.prefill"), "starved_s",
        marker="queue_empty")
