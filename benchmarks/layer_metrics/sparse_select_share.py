"""Of the block-sparse attention's device seconds in the decode program
(the scopes `attention`, `select`, `kc_write`), the share under `select`:
gathering the compressed keys through the table, their scores, the softmax
a head, the blocks' scores and the top-k, before any K or V is read. A
program whose ops carry no `select` scope gives nothing."""
from benchmarks.families.minicpm_sala import (ATTENTION_SCOPES,
                                              decode_scopes_of)


def read(rec):
    step = decode_scopes_of(rec)
    if step is None:
        return None
    by_scope, _ = step
    select_s = by_scope.get("select", 0.0)
    if select_s <= 0.0:
        return None
    return select_s / sum(by_scope.get(s, 0.0) for s in ATTENTION_SCOPES)
