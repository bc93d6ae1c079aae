"""What the benchmark reads of a boot the PROGRAM recorded itself (PR 37):
`paddle_tpu.observability.tracing`'s kept rows, one a compile request
(`compile.requests`: `t0`, `t1`, `fun_name`, `trace_s`, `lower_s`,
`backend_s`, `cache`; `trace_s` is None where the program's join found no
trace of the request's own) and one a boot span (`boot.spans`: `name`, `t0`, `t1`), on the
clock of `tracing.process_start()`. They are the process's,
not a recording's, so they are read from the store when the line is made.

A program without them (an older commit) gives None and every `setup_*`
reader leaves its metric out; nothing here raises.

"Before the window": a serve run's records carry the window's opening on
that clock (`records["program"]["window"]`); a train run's carry no
absolute time, so its set-up ends where the first stretch of at least
`records["window_s"]` without a compile request begins (none may compile
inside the window, and no part of set-up runs that long without one)."""

from __future__ import annotations

from typing import Dict, List, Optional


def load(rec: Dict) -> Optional[Dict]:
    """{"start": the process's start, "setup": the compile requests before
    the window, oldest first, "spans": the boot spans before it}, or None
    where the program keeps no such rows, keeps them in another layout, or
    the window cannot be placed."""
    try:
        from paddle_tpu.observability import tracing
        start = float(tracing.process_start())
        rows = sorted(tracing.get_records("compile.requests"),
                      key=lambda r: r["t0"])
        spans = list(tracing.get_records("boot.spans"))
        opening = _window_opening(rec, rows, float(tracing.clock())) \
            if rows else None
        if opening is None:
            return None
        setup = [r for r in rows if r["t0"] < opening]
        spans = [s for s in spans if s["t1"] <= opening]
        # every field a reader takes, asked for here: no reader raises
        for r in setup:
            r["t1"] - r["t0"] + r["lower_s"] + (r["trace_s"] or 0.0)
            r["cache"]
        for sp in spans:
            sp["name"], sp["t1"] - sp["t0"]
    except (ImportError, AttributeError, KeyError, IndexError, TypeError,
            ValueError):
        return None
    if not setup:
        return None
    return {"start": start, "setup": setup, "spans": spans}


def _window_opening(rec: Dict, rows: List[Dict], now: float
                    ) -> Optional[float]:
    if rec.get("kind") == "serve":
        program = rec.get("program")
        return float(program["window"][0]) if program else None
    window_s = rec.get("window_s")
    if rec.get("kind") != "train" or not window_s:
        return None
    end = rows[0]["t1"]
    for nxt in rows[1:] + [{"t0": now, "t1": now}]:
        if nxt["t0"] - end >= window_s:
            return end      # set-up's last request closed here
        end = max(end, nxt["t1"])
    return None


def union_seconds(rows: List[Dict]) -> float:
    """Seconds of the clock that the rows' [t0, t1] cover together."""
    covered, end = 0.0, float("-inf")
    for r in rows:          # oldest first
        covered += max(0.0, r["t1"] - max(r["t0"], end))
        end = max(end, r["t1"])
    return covered


def span_seconds(rec: Dict, kind: str, names) -> Optional[float]:
    """Seconds of the boot spans called `names` before the window of a
    `kind` cell, None where there is none."""
    boot = load(rec)
    if boot is None or rec.get("kind") != kind:
        return None
    seconds = [s["t1"] - s["t0"] for s in boot["spans"]
               if s["name"] in names]
    return sum(seconds) if seconds else None
