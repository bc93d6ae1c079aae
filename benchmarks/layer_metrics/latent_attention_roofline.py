"""Share, in percent, of the memory roofline the latent (MLA) attention of a
decode step reaches: the least bytes it must read (every resident token's
compressed vector and rotary key once a layer, 1152 B in bf16, for ALL
heads, plus the halves of W_kvb the absorbed form multiplies by;
`harness/joyai_shapes.py`, at the mean live tokens of the traced steps)
over the chip's published HBM bandwidth, divided by the decode program's
device seconds under the scope `attention` (the absorb products nest inside
it) per step in the trace. The bound is memory: the absorbed form does
about 70 kFLOP against 1152 B a token a layer, 60 FLOP/B, under the v5e's
ridge of 240 (197 TFLOP/s over 819 GB/s). A model without a latent cache
gives nothing."""
from benchmarks.harness import decode_scopes, joyai_shapes


def read(rec):
    trace, model = rec.get("trace"), rec.get("model") or {}
    attention_s = decode_scopes.step_seconds(rec, "attention")
    if attention_s is None or not rec.get("peaks") \
            or "kv_rank" not in model \
            or trace.get("live_tokens_mean") is None:
        return None
    least_s = joyai_shapes.latent_attention_min_bytes(
        model, trace["live_tokens_mean"]) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / attention_s
