"""Tokens of all chunks of the window but the single slowest, over those
chunks' time: the rate with one stray stall left out. It stands beside the
end-to-end `train_tokens_per_s`, which counts every chunk over the whole
window; where the two differ, `train_stall_share` and chunks.jsonl say by
how much and in which chunk."""


def read(rec):
    if rec.get("kind") != "train":
        return None
    return rec["rates"]["steady_tokens_per_s"]
