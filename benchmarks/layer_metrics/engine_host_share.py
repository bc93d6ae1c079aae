"""Share of the window the engine loop's thread spent inside `decode.turn`
and outside its waits for the device (`decode.prefill.wait`,
`decode.resolve.wait`): the Python of the loop, which is what holds the
chip once the device step is short."""
from benchmarks.harness import program_trace


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    w0, w1 = program["window"]
    if not any(s[0] == "decode.turn" for s in program["spans"]):
        return None
    return program_trace.loop_host_seconds(
        program["spans"], w0, w1) / (w1 - w0)
