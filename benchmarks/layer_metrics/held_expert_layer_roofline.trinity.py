"""Share, in percent, of the memory roofline the MLPs of a decode step reach
where the leading layers are dense and an expert layer holds a SHARE of its
sigmoid-routed experts beside one shared expert (Trinity): the least bytes
they must read (the dense MLPs, each expert layer's router and shared
expert, and every selected HELD expert's three matrices once, 12.6 MB at
2048 x 1024: the mean `experts_hit` of the window's decode-step records,
which counts the held experts alone; `harness/afmoe_shapes.mlp_min_bytes`)
over the chip's published HBM bandwidth, divided by the decode program's
device seconds under the scope `mlp` per step in the trace. The bound is
memory: a 32-row step gives a held expert 2 rows. A model of another family,
or a program whose step records count no experts, gives nothing."""
from benchmarks.families.afmoe import is_afmoe
from benchmarks.harness import afmoe_shapes, decode_scopes


def read(rec):
    program = rec.get("program")
    if not program or not rec.get("peaks") or not is_afmoe(rec):
        return None
    mlp_s = decode_scopes.step_seconds(rec, "mlp")
    hit = [s["experts_hit"] for s in program["steps"]
           if s["kind"] == "decode" and "experts_hit" in s]
    if mlp_s is None or not hit:
        return None
    least_s = afmoe_shapes.mlp_min_bytes(
        rec["model"], sum(hit) / len(hit)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
