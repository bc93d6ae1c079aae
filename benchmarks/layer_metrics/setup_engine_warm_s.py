"""Seconds of the engine's own boot before the window: its build
(`boot.engine_build`: casts, pools, the boot validation's traces) and its
warm-up (`boot.engine_warmup`: the phase grid, a program a phase)."""
from benchmarks.harness import boot_records


def read(rec):
    return boot_records.span_seconds(
        rec, "serve", ("boot.engine_build", "boot.engine_warmup"))
