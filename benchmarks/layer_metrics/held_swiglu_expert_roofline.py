"""Share, in percent, of the memory roofline the expert layers of a decode
step reach where an expert is three matrices (`down(silu(gate x) * up x)`),
the layer holds a SHARE of its routed experts and one shared expert sees
every row (Granite 4.0-H): the least bytes they must read (each layer's
router over all its outputs and its shared expert, and every selected HELD
expert's three matrices once, 18.87 MB at 4096 x 768: the mean `experts_hit`
of the window's decode-step records, which counts the held experts alone;
`harness/granite_hybrid_shapes.mlp_min_bytes`) over the chip's published
HBM bandwidth, divided by the decode program's device seconds under the
scope `mlp` per step in the trace. The bound is memory: a 48-row step gives
a held expert 6.7 rows. A model of another family, or a program whose step
records count no experts, gives nothing."""
from benchmarks.families.granite_hybrid import is_granite
from benchmarks.harness import decode_scopes, granite_hybrid_shapes


def read(rec):
    program = rec.get("program")
    if not program or not rec.get("peaks") or not is_granite(rec):
        return None
    mlp_s = decode_scopes.step_seconds(rec, "mlp")
    hit = [s["experts_hit"] for s in program["steps"]
           if s["kind"] == "decode" and "experts_hit" in s]
    if mlp_s is None or not hit:
        return None
    least_s = granite_hybrid_shapes.mlp_min_bytes(
        rec["model"], sum(hit) / len(hit)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
