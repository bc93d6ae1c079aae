"""95th percentile of the gap between consecutive streamed tokens of a
request at the client, over all gaps that end in the window: what
`itl_p95_ms` is where it is judged end to end. In the saturated closed
loop it is recorded only: half of the gaps hold one, two or three prefills,
so the percentile steps by a prefill's length from seed to seed (PERF.md)."""
from benchmarks.harness.window import percentile


def read(rec):
    if rec.get("kind") != "serve" or not rec["window"]["gaps_s"]:
        return None
    return 1000.0 * percentile(rec["window"]["gaps_s"], 95)
