"""Median time from when a request was DUE on the schedule to its first
streamed token at the client, over the requests due in the window. A
per-layer metric and not an end-to-end one: over the ~32 requests of a 40 s
window it spreads by 7% between seeds (PERF.md, PR 23), more than a bound
may allow."""
from benchmarks.harness.window import percentile


def read(rec):
    if rec.get("kind") != "serve" or not rec["window"]["ttft_s"]:
        return None
    return 1000.0 * percentile(rec["window"]["ttft_s"], 50)
