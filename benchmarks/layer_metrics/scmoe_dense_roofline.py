"""Share, in percent, of the memory roofline the dense MLPs of a decode
step reach where they run BESIDE a shortcut's expert path: the least bytes
they must read (gate, up and down of both sub-blocks of every layer once,
3.62 GB at 4 layers: `harness/longcat_shapes.dense_mlp_min_bytes`) over the
chip's published HBM bandwidth, divided by the decode program's device
seconds under the scope `mlp` per step in the trace (the expert path is
under `shortcut_experts`, not under `mlp`). The bound is memory: 128 rows
do 128 FLOP a weight byte, under the v5e's ridge of 240. A model of another
family gives nothing."""
from benchmarks.families.longcat import is_longcat
from benchmarks.harness import decode_scopes, longcat_shapes


def read(rec):
    if not rec.get("peaks") or not is_longcat(rec):
        return None
    mlp_s = decode_scopes.step_seconds(rec, "mlp")
    if mlp_s is None:
        return None
    least_s = longcat_shapes.dense_mlp_min_bytes(rec["model"]) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
