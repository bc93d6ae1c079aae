"""Device trace: time inside all-reduce / reduce-scatter / all-gather ops
during which no other op runs on that device, over the traced window, on
the worst device. Only where the cell spans chips."""


def read(rec):
    trace = rec.get("trace")
    if not trace or rec.get("chips", 1) < 2 or not trace.get("devices_seen"):
        return None
    return trace["collective_exposed_share_worst"]
