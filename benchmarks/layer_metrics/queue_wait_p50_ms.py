"""Median wait from a request's arrival at the engine to its admission
(the engine's own enqueued_at, read at the prefill entry) in the window."""
import statistics


def read(rec):
    if rec.get("kind") != "serve":
        return None
    waits = [s[3]["queue_wait_s"] for s in rec["spans"] if s[0] == "prefill"]
    return 1000.0 * statistics.median(waits) if waits else None
