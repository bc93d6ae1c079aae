"""Share of the window the step loop's thread spent obtaining batches
(pop of the prefetched batch, generation and device_put of the next)."""


def read(rec):
    if rec.get("kind") != "train":
        return None
    return rec["input_wait_s"] / rec["window_s"]
