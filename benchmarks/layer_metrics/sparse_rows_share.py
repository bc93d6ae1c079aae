"""Of the live rows of the window's decode steps, the share that took a
SPARSE read in the sparse layers (a context over `dense_len`): the step
records' `sparse_rows` over `live`, mean over the steps. 1.0 where every
session is over `dense_len`; under it the selection did not engage for
some rows. A program whose step records carry no such counter gives
nothing."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    shares = [s["sparse_rows"] / s["live"] for s in program["steps"]
              if s["kind"] == "decode" and "sparse_rows" in s
              and s.get("live")]
    return sum(shares) / len(shares) if shares else None
