"""95th percentile of (actual send - due time) over the requests due in
the window: how late the load generator ran."""
from benchmarks.harness.window import percentile


def read(rec):
    if rec.get("kind") != "serve" or not rec["window"]["late_s"]:
        return None
    return 1000.0 * percentile(rec["window"]["late_s"], 95)
