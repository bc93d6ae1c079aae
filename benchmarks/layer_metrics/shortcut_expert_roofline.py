"""Share, in percent, of the memory roofline the shortcut's expert path of
a decode step reaches: the least bytes it must read (every layer's router
over all 768 outputs, and every selected HELD expert's three matrices once,
75.5 MB each: the mean `experts_hit` of the window's decode-step records;
`harness/longcat_shapes.shortcut_min_bytes`) over the chip's published HBM
bandwidth, divided by the decode program's device seconds under the scope
`shortcut_experts` per step in the trace. The bound is memory: a held
expert sees two rows a step. A zero-compute expert reads nothing. A model
of another family, or a program whose records count no experts, gives
nothing."""
from benchmarks.families.longcat import SCOPE, is_longcat
from benchmarks.harness import decode_scopes, longcat_shapes


def read(rec):
    program = rec.get("program")
    if not program or not rec.get("peaks") or not is_longcat(rec):
        return None
    path_s = decode_scopes.step_seconds(rec, SCOPE)
    hit = [s["experts_hit"] for s in program["steps"]
           if s["kind"] == "decode" and "experts_hit" in s]
    if path_s is None or not hit:
        return None
    least_s = longcat_shapes.shortcut_min_bytes(
        rec["model"], sum(hit) / len(hit)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / path_s
