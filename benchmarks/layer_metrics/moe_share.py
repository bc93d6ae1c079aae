"""Share of the decode program's device time spent in the expert layer:
the seconds of its ops under the scope `mlp` (router, routing and the
grouped matmuls all nest inside it) over all of the program's seconds, from
the trace's op metadata."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes:
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    return step["by_scope"].get("mlp", 0.0) / step["total_s"]
