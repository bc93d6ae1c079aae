"""Engine-loop wall time inside prefill calls over the window (harness span
around DecodeEngine._prefill_one)."""


def read(rec):
    if rec.get("kind") != "serve":
        return None
    return sum(s[2] - s[1] for s in rec["spans"]
               if s[0] == "prefill") / rec["window_s"]
