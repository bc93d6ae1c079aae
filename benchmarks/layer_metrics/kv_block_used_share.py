"""Blocks the allocator has handed out over the blocks it can hand out,
mean over the window's decode step records (`BlockAllocator` itself, not a
sum over requests): how much of the reserved pool the traffic fills, block
rounding and reservations included."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    used = [s["blocks_used"] / s["blocks_usable"] for s in program["steps"]
            if s["kind"] in ("decode", "verify")]
    return sum(used) / len(used) if used else None
