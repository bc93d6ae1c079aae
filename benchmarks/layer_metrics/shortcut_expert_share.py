"""Share of the decode program's device time spent on the shortcut's expert
path: the seconds of its ops under the scope `shortcut_experts` (the
router over 768 outputs, the routing, the grouped matmuls over the held
experts, the zero-compute experts' one scale, the combine and the add that
ends the shortcut; a SIBLING of `mlp`, which holds the dense MLPs the path
runs beside) over all of the program's seconds, from the trace's op
metadata. A model of another family, or a program without the scope, gives
nothing."""
from benchmarks.families.longcat import SCOPE, is_longcat
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes or not is_longcat(rec):
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    seconds = step["by_scope"].get(SCOPE, 0.0)
    return seconds / step["total_s"] if seconds > 0.0 else None
