"""Share, in percent, of the memory roofline the expert layers of a decode
step reach where an expert is two matrices (`down(relu(up x)^2)`) and one
shared expert sees every row: the least bytes they must read (each layer's
router and shared expert, and every selected routed expert's two matrices
once, 19.96 MB at the published width 1856: the mean `experts_hit` of the
window's decode-step records; `harness/nemotron_h_shapes.py`) over the
chip's published HBM bandwidth, divided by the decode program's device
seconds under the scope `mlp` per step in the trace. The bound is memory: a
64-row step multiplies each expert by three rows. A program whose step
records count no experts, or a model of another expert form, gives
nothing."""
from benchmarks.harness import decode_scopes, nemotron_h_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model") or {}
    mlp_s = decode_scopes.step_seconds(rec, "mlp")
    if mlp_s is None or not program or not rec.get("peaks") \
            or "shared_dim" not in model:
        return None
    hit = [s["experts_hit"] for s in program["steps"]
           if s["kind"] == "decode" and "experts_hit" in s]
    if not hit:
        return None
    least_s = nemotron_h_shapes.mlp_min_bytes(model, sum(hit) / len(hit)) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
