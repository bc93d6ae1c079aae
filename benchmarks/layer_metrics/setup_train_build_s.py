"""Seconds of a training process's own build before the window
(`boot.train_build` around `make_train_step`'s `init_state`: the
parameters' placement and the optimizer's init program)."""
from benchmarks.harness import boot_records


def read(rec):
    return boot_records.span_seconds(rec, "train", ("boot.train_build",))
