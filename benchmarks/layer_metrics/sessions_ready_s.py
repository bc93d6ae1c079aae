"""Seconds from the schedule's start `t0`, when every session of a
`sessions` cell is sent, to the LAST session's first token at its client:
the time the engine takes to prefill the whole pool, which the traffic's
`lead_s` has to cover and the only place beside `setup_s` where a change to
the prefill path shows in such a cell. A `serve` cell's records give
nothing."""


def read(rec):
    return (rec.get("sessions") or {}).get("ready_s")
