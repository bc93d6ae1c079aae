"""Share, in percent, of the memory roofline the dense SwiGLUs of a decode
step reach: the least bytes they must read (gate, up and down of every
layer once: `harness/minicpm_sala_shapes.dense_mlp_min_bytes`; a few rows
of activations are nothing beside 403 MB a layer) over the chip's published
HBM bandwidth, divided by the decode program's device seconds under the
scope `mlp` per step in the trace: what the rest of a step of this model
is worth, beside its two attention mechanisms. The bound is memory: 32
rows do 32 FLOP a weight byte, against a ridge of 240. A model of
another family (no `mlp_dim` beside `mixers`) gives nothing."""
from benchmarks.harness import decode_scopes, minicpm_sala_shapes


def read(rec):
    model = rec.get("model") or {}
    if not rec.get("peaks") or "mixers" not in model \
            or "mlp_dim" not in model:
        return None
    mlp_s = decode_scopes.step_seconds(rec, "mlp")
    if mlp_s is None:
        return None
    least_s = minicpm_sala_shapes.dense_mlp_min_bytes(model) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mlp_s
