"""Engine-loop wall time inside the program's `decode.prefill` spans (one
request's prompt work: a whole-prompt prefill, or one chunk of it) over the
window (the span holds the wait for the step in flight)."""
from benchmarks.harness import program_trace


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    w0, w1 = program["window"]
    return program_trace.span_seconds(
        program["spans"], "decode.prefill", w0, w1) / (w1 - w0)
