"""Median over the window's chunks of the chunk's time per step; every
chunk ends in block_until_ready on its last loss."""
import statistics


def read(rec):
    if rec.get("kind") != "train":
        return None
    return 1000.0 * statistics.median(
        c["seconds"] / c["steps"] for c in rec["chunks"])
