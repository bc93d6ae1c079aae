"""Median time from one decode dispatch of the engine loop to the next,
inside the window (harness span around DecodeEngine._dispatch): the decode
step as the engine loop lives it, prefill interruptions being the minority
the median passes over."""
import statistics


def read(rec):
    if rec.get("kind") != "serve":
        return None
    starts = [s[1] for s in rec["spans"] if s[0] == "engine_dispatch"]
    if len(starts) < 3:
        return None
    return 1000.0 * statistics.median(
        b - a for a, b in zip(starts, starts[1:]))
