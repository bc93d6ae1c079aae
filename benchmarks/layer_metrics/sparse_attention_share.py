"""Share of the decode program's device time spent in the block-sparse
attention: the seconds of its ops under the scopes `attention`, `select`
(the compressed keys gathered and scored, the top-k) and `kc_write` (the
compressed key a token completes), the last two nested inside the first,
over all of the program's seconds, from the trace's op metadata. A model
without sparse layers, or a program whose ops carry no `select` scope,
gives nothing."""
from benchmarks.families.minicpm_sala import (ATTENTION_SCOPES,
                                              decode_scopes_of)


def read(rec):
    step = decode_scopes_of(rec)
    if step is None or "mixers" not in (rec.get("model") or {}):
        return None
    by_scope, total_s = step
    if by_scope.get("select", 0.0) <= 0.0:
        return None
    return sum(by_scope.get(s, 0.0) for s in ATTENTION_SCOPES) / total_s
