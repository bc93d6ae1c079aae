"""Share, in percent, of the memory roofline the expert layers of a decode
step reach: the least bytes they must read (every selected expert's three
matrices once: the mean `experts_hit` of the window's decode-step records,
plus the routers; `harness/olmoe_shapes.py`) over the chip's published HBM
bandwidth, divided by the decode program's device seconds under the scope
`mlp` per step in the trace. The bound is memory: a 16-row step multiplies
each expert's 12.6 MB by a few rows. A program whose step records count no
experts gives nothing."""
from benchmarks.harness import olmoe_shapes, program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    program, trace = rec.get("program"), rec.get("trace")
    if rec.get("kind") != "serve" or not scopes or not program \
            or not rec.get("peaks"):
        return None
    hit = [s["experts_hit"] for s in program["steps"]
           if s["kind"] == "decode" and "experts_hit" in s]
    names = [n for n in scopes["programs"]
             if "decode" in n and n in trace.get("modules", {})]
    if not hit or not names:
        return None
    name = max(names, key=lambda n: scopes["programs"][n]["total_s"])
    mlp_s = scopes["programs"][name]["by_scope"].get("mlp", 0.0) \
        / trace["modules"][name]["count"]
    if mlp_s <= 0.0:
        return None
    least_s = olmoe_shapes.moe_layer_min_bytes(
        rec["model"], sum(hit) / len(hit)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
