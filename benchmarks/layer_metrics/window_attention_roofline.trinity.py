"""Share, in percent, of the memory roofline the SLIDING layers' attention
of a decode step reaches (Trinity: the paged walk with a start, over the
window kind's pools): the least bytes it must read (the keys the step's
windows hold, `window_tokens` of the window's decode-step records: sum over
the slots of min(position + 1, window), K and V once a sliding layer, 2048 B
a token a layer; `harness/afmoe_shapes.window_min_bytes`) over the chip's
published HBM bandwidth, divided by the decode program's device seconds
under the scope `window_attention` per step in the trace. The bound is
memory. A model of another family, or a program whose step records carry no
`window_tokens`, gives nothing."""
from benchmarks.families.afmoe import is_afmoe
from benchmarks.harness import afmoe_shapes, decode_scopes


def read(rec):
    program = rec.get("program")
    if not program or not rec.get("peaks") or not is_afmoe(rec):
        return None
    seconds = decode_scopes.step_seconds(rec, "window_attention")
    held = [s["window_tokens"] for s in program["steps"]
            if s["kind"] == "decode" and "window_tokens" in s]
    if seconds is None or not held:
        return None
    least_s = afmoe_shapes.window_min_bytes(
        rec["model"], sum(held) / len(held)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
