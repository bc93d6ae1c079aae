"""Share, in percent, of the memory roofline the recurrent (Mamba-2) layers
of a decode step reach: the least bytes they must move (their weights once,
and the convolution's tail and the float32 SSM state of EVERY row the step
runs, read and written once: the device computes idle rows too;
`harness/nemotron_h_shapes.py`, at the mean `slots` of the window's
decode-step records) over the chip's published HBM bandwidth, divided by
the decode program's device seconds under the scope `ssm` per step in the
trace. The bound is memory: a row's state is 2.1 MB a layer and a token
does 4 FLOP a value of it. A model without recurrent layers gives
nothing."""
from benchmarks.harness import decode_scopes, nemotron_h_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model") or {}
    ssm_s = decode_scopes.step_seconds(rec, "ssm")
    if ssm_s is None or not program or not rec.get("peaks") \
            or "ssm_heads" not in model:
        return None
    slots = [s["slots"] for s in program["steps"] if s["kind"] == "decode"]
    if not slots:
        return None
    least_s = nemotron_h_shapes.ssm_step_min_bytes(
        model, sum(slots) / len(slots)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / ssm_s
