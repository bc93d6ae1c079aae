"""End-to-end model FLOP utilization in percent: `train_tokens_per_s` (all
chunks over the whole window) x forward+backward FLOPs per token (shape
function of the benchmark) over chips x the chip's published bf16 peak.
Not a kernel's roofline share."""


def read(rec):
    if rec.get("kind") != "train" or not rec.get("peaks"):
        return None
    return 100.0 * (rec["rates"]["tokens_per_s"] * rec["flops_per_token"]
                    / (rec["chips"] * rec["peaks"]["bf16_flops_per_s"]))
