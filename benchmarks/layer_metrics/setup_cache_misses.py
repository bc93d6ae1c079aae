"""Compile requests before the window that the persistent cache did not
answer (`cache` not "hit"): 0 in a warm checkout; over 0 names an eviction,
a key that moved, or a program the cache does not keep."""
from benchmarks.harness import boot_records


def read(rec):
    boot = boot_records.load(rec)
    if boot is None:
        return None
    return sum(r["cache"] != "hit" for r in boot["setup"])
