"""Median wait from a request's arrival in the engine's queue to its
admission, from the program's request records (`enqueued_at` to
`admitted_at`) of the requests that finished in the window."""
import statistics


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    waits = [r["admitted_at"] - r["enqueued_at"]
             for r in program["requests"] if r["admitted_at"]]
    return 1000.0 * statistics.median(waits) if waits else None
