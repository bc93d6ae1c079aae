"""Share of the streams' token gaps in the window that hold an admission's
prefill: intervals from one decode step to the next (the program's
`decode.steps` records) with the start of a `decode.prefill` span inside
them, each weighted by the sequences live in the step that opens it. It
says on which side of the two-mode gap distribution (a bare decode step,
or a step plus prefills) `itl_p95_ms` stands: in the upper mode while this
is over 0.05."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    steps = sorted((s["t"], s["live"]) for s in program["steps"]
                   if s["kind"] in ("decode", "verify"))
    fills = sorted(s[1] for s in program["spans"]
                   if s[0] == "decode.prefill")
    if len(steps) < 3:
        return None
    held = total = 0
    i = 0
    for (a, live), (b, _) in zip(steps, steps[1:]):
        while i < len(fills) and fills[i] < a:
            i += 1
        total += live
        if i < len(fills) and fills[i] < b:
            held += live
    return held / total if total else None
