"""Share of the decode program's device time spent in the recurrent
(Mamba-2) layers: the seconds of its ops under the scope `ssm` (the input
projection, the convolution, the state update and the gated output
projection all nest inside it) over all of the program's seconds, from the
trace's op metadata. A model without recurrent layers, or a program whose
ops carry no such scope, gives nothing."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes \
            or "ssm_heads" not in (rec.get("model") or {}):
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    ssm_s = step["by_scope"].get("ssm", 0.0)
    return ssm_s / step["total_s"] if ssm_s > 0.0 else None
