"""Share, in percent, of the memory roofline the FULL layers' attention of a
decode step reaches (Trinity: the paged grouped-query walk from block 0, over
the global kind's pools): the least bytes it must read (every resident
token's K and V once a full layer, 2048 B a token a layer, at the mean live
tokens of the traced steps; `harness/afmoe_shapes.attention_min_bytes`) over
the chip's published HBM bandwidth, divided by the decode program's device
seconds under the scope `attention` per step in the trace. The bound is
memory. A model of another family gives nothing."""
from benchmarks.families.afmoe import is_afmoe
from benchmarks.harness import afmoe_shapes, decode_scopes


def read(rec):
    trace = rec.get("trace")
    seconds = decode_scopes.step_seconds(rec, "attention")
    if seconds is None or not rec.get("peaks") or not is_afmoe(rec) \
            or trace.get("live_tokens_mean") is None:
        return None
    least_s = afmoe_shapes.attention_min_bytes(
        rec["model"], trace["live_tokens_mean"]) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
