"""Blocks the WINDOW kind's allocator has handed out over the blocks it can
hand out (a ring a slot), mean over the window's decode step records
(`window_blocks_used` over `window_blocks_usable`): 1.0 where every slot
holds a sequence past the window, less where sequences shorter than the
window hold their own length. A program whose step records carry no
`window_blocks_used` (a model of one cache kind) gives nothing."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    used = [s["window_blocks_used"] / s["window_blocks_usable"]
            for s in program["steps"]
            if s["kind"] == "decode" and s.get("window_blocks_usable")]
    return sum(used) / len(used) if used else None
