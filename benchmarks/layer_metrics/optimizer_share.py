"""Device time under the scope `optimizer` (the optax update and the
parameter write of `make_train_step`, `clip` included) over device busy
time, in the traced steps."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "train" or not scopes:
        return None
    by = scopes["by_scope"]
    return (by.get("optimizer", 0.0) + by.get("clip", 0.0)) \
        / scopes["busy_s"]
