"""State rows the allocator has handed out over the rows it can hand out
(`StateRowAllocator` itself: the null row is in neither), mean over the
window's decode step records: how much of the recurrent-state pool the
traffic fills. A program whose step records carry no such counter (a model
without recurrent state) gives nothing."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    used = [s["state_rows_used"] / s["state_rows"] for s in program["steps"]
            if s["kind"] == "decode" and s.get("state_rows")]
    return sum(used) / len(used) if used else None
