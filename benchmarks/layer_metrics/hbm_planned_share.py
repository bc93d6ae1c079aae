"""The compiler's plan (temporaries of the window's largest program) plus
the arrays resident during the window on the fullest chip, over the chip's
HBM: what is ALLOCATED, the reserved KV pool whole (`kv_block_used_share` says
how much of it holds tokens). Not the runtime's peak_bytes_in_use, which
leaves temporaries out. One reader for `.train` and `.serve`."""


def read(rec):
    if not rec.get("peaks") or rec.get("planned_bytes") is None:
        return None
    return rec["planned_bytes"] / rec["peaks"]["hbm_bytes"]
