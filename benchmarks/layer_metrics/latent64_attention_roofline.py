"""Share, in percent, of its roofline the 64-head latent (MLA) attention of
a decode step reaches: the larger of the time its least bytes take at the
chip's published HBM bandwidth (every resident token's compressed vector
and rotary key once a CACHE layer, two a layer, plus the halves of W_kvb
the absorbed form multiplies by) and the time its operations take at the
published bf16 peak (`harness/longcat_shapes.py`, at the mean live tokens
of the traced steps and the mean `slots` of the window's decode records),
divided by the decode program's device seconds under the scope `attention`
(the absorb products nest inside it) per step in the trace. At 64 heads
the absorbed form does 139 kFLOP against 1152 B a token a cache layer, 121
FLOP/B: memory still binds, with half the margin 32 heads have. A model of
another family gives nothing (`latent_attention_roofline` reads 32 heads
through `joyai_shapes`)."""
from benchmarks.families.longcat import is_longcat
from benchmarks.harness import decode_scopes, longcat_shapes


def read(rec):
    trace, program = rec.get("trace"), rec.get("program")
    if not rec.get("peaks") or not is_longcat(rec) or not trace \
            or not program or trace.get("live_tokens_mean") is None:
        return None
    attention_s = decode_scopes.step_seconds(rec, "attention")
    slots = [s["slots"] for s in program["steps"] if s["kind"] == "decode"]
    if attention_s is None or not slots:
        return None
    model, live = rec["model"], trace["live_tokens_mean"]
    least_s = max(
        longcat_shapes.latent_attention_min_bytes(model, live)
        / rec["peaks"]["hbm_bytes_per_s"],
        longcat_shapes.latent_attention_flops(
            model, live, sum(slots) / len(slots))
        / rec["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / attention_s
