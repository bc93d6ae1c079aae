"""Seconds of the clock covered by the compile requests before the window
(the union of their `[t0, t1]`): tracing, lowering, the cache's answer and
the backend, everything set-up spent asking for executables."""
from benchmarks.harness import boot_records


def read(rec):
    boot = boot_records.load(rec)
    if boot is None:
        return None
    return boot_records.union_seconds(boot["setup"])
