"""Of the window of a `sessions` cell, the share of time that lies in a gap
of over 100 ms in which NO client received a token (`kinds/sessions.py
silences`, from `requests.jsonl`; the window's edges close a gap).
With every session decoding, some client receives a token every step, so
such a gap is a pause of the machine, the engine or the load generator as
the clients feel it: what a refusal for noise was made of. A `serve` cell's
records give nothing."""


def read(rec):
    sessions = rec.get("sessions") or {}
    if sessions.get("silence_s") is None or not rec.get("window_s"):
        return None
    return sessions["silence_s"] / rec["window_s"]
