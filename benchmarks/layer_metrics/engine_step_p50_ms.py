"""Median time from the start of one decode step to the start of the next,
inside the window, from the PROGRAM's step records (kind `decode`): the
decode step as the engine loop lives it, prefill interruptions being the
minority the median passes over."""
import statistics


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    starts = [s["t"] for s in program["steps"]
              if s["kind"] in ("decode", "verify")]
    if len(starts) < 3:
        return None
    return 1000.0 * statistics.median(
        b - a for a, b in zip(starts, starts[1:]))
