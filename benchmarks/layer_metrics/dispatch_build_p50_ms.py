"""Median `decode.dispatch.build` in the window: the host's part of a
dispatch before anything is enqueued (the batch's snapshot, positions, a
block table a slot, where the ids come from). What a batch whose rows stay
put between steps would save."""
from benchmarks.harness import loop_records


def read(rec):
    return loop_records.median_ms(rec, "decode.dispatch.build")
