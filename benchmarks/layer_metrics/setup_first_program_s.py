"""Seconds from the process's start (`tracing.process_start()`) to the
first compile request's beginning: the interpreter, the imports and the
backend's start-up, before any program of the run is asked for."""
from benchmarks.harness import boot_records


def read(rec):
    boot = boot_records.load(rec)
    if boot is None:
        return None
    return boot["setup"][0]["t0"] - boot["start"]
