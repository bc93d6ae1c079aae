"""Share of the decode program's device time spent in the recurrent
(Mamba-1) layers: the seconds of its ops under the scope `ssm` (the
projections, the convolution, the selective state update and the gated
output projection all nest inside it) over all of the program's seconds,
from the trace's op metadata. A model of another family, or a program
whose ops carry no such scope (the parent's), gives nothing."""
from benchmarks.families.jamba import is_jamba
from benchmarks.families.minicpm_sala import decode_scopes_of


def read(rec):
    step = decode_scopes_of(rec) if is_jamba(rec) else None
    if step is None:
        return None
    by_scope, total_s = step
    ssm_s = by_scope.get("ssm", 0.0)
    return ssm_s / total_s if ssm_s > 0.0 else None
