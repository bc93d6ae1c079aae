"""Sum of `trace_s + lower_s` of the compile requests before the window:
what no cache saves, and where a Pallas kernel's lowering to Mosaic shows
(it happens in `jit.lower()`, before the persistent cache is asked). A
request with no trace of its own (`trace_s` None: the program's join did
not find it) adds its lowering alone, and the program counts those
(`status()["boot"]["compile"]["untraced"]`)."""
from benchmarks.harness import boot_records


def read(rec):
    boot = boot_records.load(rec)
    if boot is None:
        return None
    return sum((r["trace_s"] or 0.0) + r["lower_s"] for r in boot["setup"])
