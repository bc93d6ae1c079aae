"""Device time under the scope `attention` (scores, softmax, context;
forward and backward) over device busy time, in the traced steps."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "train" or not scopes:
        return None
    return scopes["by_scope"].get("attention", 0.0) / scopes["busy_s"]
