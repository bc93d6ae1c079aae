"""Share, in percent, of the memory roofline the grouped-query attention of
a decode step reaches: the least bytes it must read (every resident token's
K and V once an attention layer, for ALL the query heads of a K/V head:
1024 B a token at 2 K/V heads of 128 in bf16; `harness/nemotron_h_shapes.py`,
at the mean live tokens of the traced steps) over the chip's published HBM
bandwidth, divided by the decode program's device seconds under the scope
`attention` per step in the trace. The bound is memory: 16 query heads do
8 kFLOP against 512 B a token a K/V head, 16 FLOP/B. A model whose K/V
heads are as many as its query heads gives nothing."""
from benchmarks.harness import decode_scopes, nemotron_h_shapes


def read(rec):
    trace, model = rec.get("trace"), rec.get("model") or {}
    attention_s = decode_scopes.step_seconds(rec, "attention")
    if attention_s is None or not rec.get("peaks") \
            or model.get("kv_heads") in (None, model.get("heads")) \
            or trace.get("live_tokens_mean") is None:
        return None
    least_s = nemotron_h_shapes.attention_min_bytes(
        model, trace["live_tokens_mean"]) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / attention_s
