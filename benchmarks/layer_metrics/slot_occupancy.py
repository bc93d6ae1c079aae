"""Mean live slots over the compiled slot count per decode step in the
window, from the engine's OCCUPANCY histogram (sum and count at the
window's edges)."""


def read(rec):
    if rec.get("kind") != "serve":
        return None
    a = rec["counters"]["open"]["occupancy"]
    b = rec["counters"]["close"]["occupancy"]
    n = b["count"] - a["count"]
    return (b["sum"] - a["sum"]) / n if n > 0 else None
