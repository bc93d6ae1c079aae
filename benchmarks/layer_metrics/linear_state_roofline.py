"""Share, in percent, of the memory roofline the lightning (linear
attention) layers of a decode step reach: the least bytes they must move
(their mixers' weights once, and the `heads x D x D` float32 state of
EVERY row the step runs read and written once: the device computes idle
rows too; `harness/minicpm_sala_shapes.linear_state_min_bytes`, at the mean
`slots` of the window's decode-step records) over the chip's published HBM
bandwidth, divided by the decode program's device seconds under the scope
`ssm` per step in the trace. The bound is memory: a row's state is 2.1 MB a
layer and a token does 4 FLOP a value of it. A model without lightning
layers gives nothing."""
from benchmarks.harness import decode_scopes, minicpm_sala_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model") or {}
    if not program or not rec.get("peaks") or "lin_heads" not in model:
        return None
    ssm_s = decode_scopes.step_seconds(rec, "ssm")
    slots = [s["slots"] for s in program["steps"] if s["kind"] == "decode"]
    if ssm_s is None or not slots:
        return None
    least_s = minicpm_sala_shapes.linear_state_min_bytes(
        model, sum(slots) / len(slots)) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / ssm_s
