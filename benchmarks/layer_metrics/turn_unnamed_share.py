"""Share of the engine loop's turns, in seconds, that lies under NO child
span of `decode.turn` (by `parent`: `decode.admit`, `decode.grow`,
`decode.dispatch`, `decode.flush`, `decode.resolve`): what the loop's own
spans cannot name, the tracer's open and close around each child included.
Reads any program that records `decode.turn`, so the parent's too."""
from benchmarks.harness import loop_records


def read(rec):
    loop = loop_records.load(rec)
    if loop is None:
        return None
    seconds = sum(t["t1"] - t["t0"] for t in loop["turns"])
    if seconds <= 0:
        return None
    return (seconds - sum(t["named"] for t in loop["turns"])) / seconds
