"""Share of the decode program's device time spent in the latent (MLA)
attention: the seconds of its ops under the scope `attention` (the paged
kernel over the latent pool and the absorb products W_UK, W_UV that nest
inside it) over all of the program's seconds, from the trace's op metadata.
A model without a latent cache gives nothing."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes \
            or "kv_rank" not in (rec.get("model") or {}):
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    return step["by_scope"].get("attention", 0.0) / step["total_s"]
