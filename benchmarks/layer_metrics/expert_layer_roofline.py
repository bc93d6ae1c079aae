"""Share, in percent, of the memory roofline the second halves of a decode
step's blocks reach where a dense layer leads and the others are sparse
with a shared expert: the least bytes they must read (the dense layer's
three matrices, each expert layer's router and shared expert, and every
selected routed expert's three matrices once: the mean `experts_hit` of the
window's decode-step records; `harness/joyai_shapes.py`) over the chip's
published HBM bandwidth, divided by the decode program's device seconds
under the scope `mlp` per step in the trace. The bound is memory: a 32-row
step multiplies each expert's 9.4 MB by a row or two. A program whose step
records count no experts, or a model without leading dense layers, gives
nothing (`moe_layer_roofline` is the reader of a model whose layers are all
sparse)."""
from benchmarks.harness import decode_scopes, joyai_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model") or {}
    mlp_s = decode_scopes.step_seconds(rec, "mlp")
    if mlp_s is None or not program or not rec.get("peaks") \
            or "dense_layers" not in model:
        return None
    hit = [s["experts_hit"] for s in program["steps"]
           if s["kind"] == "decode" and "experts_hit" in s]
    if not hit:
        return None
    least_s = joyai_shapes.mlp_min_bytes(model, sum(hit) / len(hit)) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
