"""Share of the decode program's device time spent in the lightning (linear
attention) layers: the seconds of its ops under the scope `ssm` (the
projections, the rotary, the state update and the gated output projection
all nest inside it) over all of the program's seconds, from the trace's op
metadata. A model without lightning layers gives nothing."""
from benchmarks.families.minicpm_sala import decode_scopes_of


def read(rec):
    step = decode_scopes_of(rec)
    if step is None or "lin_heads" not in (rec.get("model") or {}):
        return None
    by_scope, total_s = step
    ssm_s = by_scope.get("ssm", 0.0)
    return ssm_s / total_s if ssm_s > 0.0 else None
