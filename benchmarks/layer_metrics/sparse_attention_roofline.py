"""Share, in percent, of the memory roofline the block-sparse attention of a
decode step reaches: the least bytes it must read (`harness/
minicpm_sala_shapes.sparse_attention_min_bytes`: the compressed keys the
rows over `dense_len` score, 512 B a complete window, the K and V of the
blocks they take, 1024 B a token, and every live token of the rows that
read everything; a sparse layer, from the counters of the window's decode
step records: `kc_entries`, `sparse_rows`, `blocks_selected`,
`dense_tokens`) over the chip's published HBM bandwidth, divided by the
decode program's device seconds under the scopes `attention`, `select` and
`kc_write` (the last two nest inside the first) per step in the trace. The
bound is memory: 16 query heads do 8 kFLOP against 512 B a token a K/V
head. A program whose step records carry no such counters gives nothing."""
from benchmarks.families.minicpm_sala import ATTENTION_SCOPES
from benchmarks.harness import decode_scopes, minicpm_sala_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model") or {}
    if not program or not rec.get("peaks") or "mixers" not in model:
        return None
    steps = [s for s in program["steps"]
             if s["kind"] == "decode" and "sparse_rows" in s]
    parts = [decode_scopes.step_seconds(rec, s) for s in ATTENTION_SCOPES]
    if not steps or parts[0] is None:
        return None
    least = sum(minicpm_sala_shapes.sparse_attention_min_bytes(
        model, s["kc_entries"], s["sparse_rows"], s["blocks_selected"],
        s.get("dense_tokens", 0)) for s in steps) / len(steps)
    return 100.0 * least / rec["peaks"]["hbm_bytes_per_s"] \
        / sum(p or 0.0 for p in parts)
