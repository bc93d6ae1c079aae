"""Share, in percent, of the memory roofline the recurrent (Mamba-1) layers
of a decode step reach: the least bytes they must move (their weights once,
and the convolution's tail and the float32 state of EVERY row the step
runs, read and written once: the device computes idle rows too;
`harness/jamba_shapes.ssm_step_min_bytes`, at the mean `slots` of the
window's decode-step records) over the chip's published HBM bandwidth,
divided by the decode program's device seconds under the scope `ssm` per
step in the trace. The bound is memory: a row's state is 320 KB a layer and
a token does about 7 operations a value of it. A model of another family
gives nothing."""
from benchmarks.families.jamba import is_jamba
from benchmarks.harness import decode_scopes, jamba_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model")
    if not program or not rec.get("peaks") or not is_jamba(rec):
        return None
    ssm_s = decode_scopes.step_seconds(rec, "ssm")
    slots = [s["slots"] for s in program["steps"] if s["kind"] == "decode"]
    if ssm_s is None or not slots:
        return None
    least_s = jamba_shapes.ssm_step_min_bytes(
        model, sum(slots) / len(slots)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / ssm_s
