"""Of the blocks the sparse rows of the window's decode steps read in a
sparse layer, the share that EVERY K/V head of a row reads whatever it
scores (the first blocks and the newest window's), which the walk fetches
once for all heads at a block's full lanes: the step records'
`shared_entries` over `blocks_selected` x `sparse_rows` (both in selection
blocks), summed over the steps whose live rows are all over `dense_len` (a
row under it shares its whole list and takes no selection). 33-34 of 64
where the window is 2048 tokens of 64-token blocks; 0 would say the walk's
both-heads copy never engaged. A program whose step records carry no such
counter gives nothing."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    steps = [s for s in program["steps"]
             if s["kind"] == "decode" and "shared_entries" in s
             and s.get("sparse_rows") and not s.get("dense_tokens")]
    taken = sum(s["blocks_selected"] * s["sparse_rows"] for s in steps)
    return sum(s["shared_entries"] for s in steps) / taken if taken else None
