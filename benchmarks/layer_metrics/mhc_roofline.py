"""Share, in percent, of the memory roofline the residual path of a decode
step reaches: the least bytes it must move (a sub-layer's maps once, the
step's rows' streams read once and written once a sub-layer:
`harness/xing4_shapes.mhc_min_bytes`, at the slots of the window's decode
steps) over the chip's published HBM bandwidth, divided by the decode
program's device seconds under the scopes `mhc*` per step in the trace. The
bound is memory in name only: 24 small stages a step of 2.5 MB each, every
one a chain of a reduction, a 24-column product, 20 rounds on 4 x 4 numbers
a row and two mixes, so what it reads is how far latency, not bandwidth,
holds the path. A model without `hc_mult`, or a program whose ops carry no
such scope, gives nothing."""
from benchmarks.families.xing4 import MHC_SCOPES
from benchmarks.harness import decode_scopes, xing4_shapes


def read(rec):
    model, program = rec.get("model") or {}, rec.get("program")
    if "hc_mult" not in model or not rec.get("peaks") or not program:
        return None
    mhc_s = sum(decode_scopes.step_seconds(rec, s) or 0.0 for s in MHC_SCOPES)
    slots = [s["slots"] for s in program["steps"] if s["kind"] == "decode"]
    if not mhc_s or not slots:
        return None
    least_s = xing4_shapes.mhc_min_bytes(model, max(slots)) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mhc_s
