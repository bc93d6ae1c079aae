"""Share of the window the engine loop's thread wanted the processor or the
interpreter lock inside a turn and did not have it: a turn's wall less its
`.wait` spans (it asked to sleep there) less its `cpu_s`, summed. The
readers' threads and one interpreter lock show here; a wait that spins
counts against it."""
from benchmarks.harness import loop_records


def read(rec):
    loop = loop_records.load(rec)
    if loop is None or not loop["inside"]:
        return None
    return sum(t["t1"] - t["t0"] - t["wait_s"] - t["cpu_s"]
               for t in loop["turns"]) / loop["window_s"]
