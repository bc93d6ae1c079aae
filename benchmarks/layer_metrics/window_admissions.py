"""Prompts the engine admitted INSIDE the window of a `sessions` cell: the
PROGRAM's step records of a prompt's programs (kind `prefill`, or `chunk`
under chunked prefill) whose time lies in the window. 0, or the run is void
(`kinds/sessions.py` then reports `correct: false`): the kind measures
sessions that were prefilled during set-up. A `serve` cell's records (no
`sessions` key) and an untraced run (no recording) give nothing."""
from benchmarks.kinds.sessions import ADMISSION_STEPS


def read(rec):
    program = rec.get("program")
    if not rec.get("sessions") or not program:
        return None
    return sum(1 for s in program["steps"] if s["kind"] in ADMISSION_STEPS)
