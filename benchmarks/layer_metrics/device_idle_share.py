"""Profiler trace of a steady window: 1 - union of op intervals / window,
on the worst device. One reader for `device_idle_share.train` and
`device_idle_share.serve`: the manifest splits the quantity by the
end-to-end metric it moves, not by how it is read."""


def read(rec):
    trace = rec.get("trace")
    if not trace or not trace.get("devices_seen"):
        return None
    return trace["idle_share_worst"]
