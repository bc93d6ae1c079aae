"""Share of the decode program's device time spent under the layer scopes
that compute the model (`ln`, `qkv`, `attention`, `proj`, `mlp`, `head`,
`embed`); the rest moves the KV pool (`kv_write`, `kv_gather`, the scan's
`layers.carry`) or carries no scope. From the trace's op metadata."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes:
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    return sum(step["by_scope"].get(s, 0.0)
               for s in program_trace.COMPUTE) / step["total_s"]
