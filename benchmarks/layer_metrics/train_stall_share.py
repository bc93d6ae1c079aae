"""1 - median chunk time x chunks / window: what a stall, or any chunk
slower than the median, cost the end-to-end rate."""


def read(rec):
    if rec.get("kind") != "train":
        return None
    return rec["rates"]["stall_share"]
