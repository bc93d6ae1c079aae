"""What the benchmark reads of the engine loop's turn as the PROGRAM records
it from the inside (PR 56): the `decode.turn` spans of the window with their
children by `parent` (`decode.admit`, `decode.grow`, `decode.dispatch` with
its `.build` and `.call`, `decode.flush`, `decode.resolve`), the `.wait`
spans inside them, the facts `cpu_s` (a turn's CPU seconds on the loop's
thread), `queue_empty` / `starved_s` (the host saw the device's queue empty
before a dispatch or an admission's prefill) and `same_bucket_waiting` (an
admission's company), and the record list `host.hiccups` (the recording's
second thread woke late: `t`, `late_s`). Spans come from
`rec["program"]["spans"]`, as `program_trace.collect` clipped them to the
window; the hiccups are read from the program's store when the line is made,
clipped to `rec["program"]["window"]`, as `boot_records` reads
`compile.requests`.

A record without them (a train cell, an untraced run, an older commit whose
turns carry no `cpu_s`) gives None or leaves the part out, and every reader
of it leaves its metric out; nothing here raises."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from . import trace_reduce

Interval = Tuple[float, float]

# a turn is LONG by what it exceeds this many seconds plus LONG_MEDIANS
# times the window's median turn: a pause, not a slow step
LONG_S = 0.05
LONG_MEDIANS = 3.0


def load(rec: Dict) -> Optional[Dict]:
    """{"window_s", "turns": [{"t0", "t1", "cpu_s" | None, "named": the
    seconds of the turn its children cover, "wait_s", "waits": the `.wait`
    intervals inside it}], "spans": {name: [(t0, t1, facts)]}, "hiccups":
    [(t, t + late_s)], "inside": whether the turns carry `cpu_s`, i.e. the
    program records its turn from the inside}, or None where the record
    holds no turn of a served window."""
    try:
        program = rec.get("program") if rec.get("kind") == "serve" else None
        if not program:
            return None
        w0, w1 = (float(x) for x in program["window"])
        by_name: Dict[str, List] = {}
        children: Dict[int, List[Interval]] = {}
        for name, a, b, _tid, facts in program["spans"]:
            by_name.setdefault(name, []).append((float(a), float(b), facts))
            if facts.get("parent") is not None:
                children.setdefault(facts["parent"], []).append(
                    (float(a), float(b)))
        waits = trace_reduce.union(
            (a, b) for name, rows in by_name.items()
            if name.startswith("decode.") and name.endswith(".wait")
            for a, b, _ in rows)
        turns = []
        for a, b, facts in sorted(by_name.get("decode.turn", ()),
                                  key=lambda s: s[0]):
            held = trace_reduce.clip(waits, a, b)
            cpu_s = facts.get("cpu_s")
            turns.append({
                "t0": a, "t1": b,
                "cpu_s": None if cpu_s is None else float(cpu_s),
                "named": trace_reduce.total(trace_reduce.clip(
                    trace_reduce.union(children.get(facts["sid"], ())),
                    a, b)),
                "waits": held, "wait_s": trace_reduce.total(held)})
        if not turns or w1 <= w0:
            return None
        inside = all(t["cpu_s"] is not None for t in turns)
    except (AttributeError, KeyError, IndexError, TypeError, ValueError):
        return None
    return {"window_s": w1 - w0, "turns": turns, "spans": by_name,
            "hiccups": _hiccups(w0, w1) if inside else [],
            "inside": inside}


def _hiccups(w0: float, w1: float) -> List[Interval]:
    try:
        from paddle_tpu.observability import tracing
        rows = tracing.get_records("host.hiccups")
    except (ImportError, AttributeError):
        return []
    late = []
    for r in rows:
        try:
            if w0 <= r["t"] < w1:
                late.append((float(r["t"]), float(r["t"] + r["late_s"])))
        except (KeyError, TypeError, ValueError):
            continue    # a row in another layout owns nothing
    return trace_reduce.union(late)


def median_ms(rec: Dict, name: str) -> Optional[float]:
    """Median duration of the window's spans called `name`, ms."""
    loop = load(rec)
    rows = loop["spans"].get(name) if loop else None
    if not rows:
        return None
    return 1000.0 * statistics.median(b - a for a, b, _ in rows)


def fact_share(rec: Dict, names, fact: str, marker: Optional[str] = None
               ) -> Optional[float]:
    """The sum of the numeric fact `fact` over the window's spans called
    `names`, over the window; None where no such span carries `marker`
    (the fact itself without one): a program that does not record it."""
    loop = load(rec)
    if loop is None:
        return None
    rows = [facts for name in names
            for _, _, facts in loop["spans"].get(name, ())]
    try:
        if not any((marker or fact) in f for f in rows):
            return None
        return sum(float(f.get(fact) or 0.0) for f in rows) \
            / loop["window_s"]
    except (TypeError, ValueError):
        return None


def pauses(rec: Dict) -> Optional[Dict]:
    """The window's long turns, owned: {"window_s", "excess_s": the
    seconds by which turns exceed LONG_S + LONG_MEDIANS x the median turn,
    "host_s": of them, those a hiccup's [t, t + late_s] overlaps (no Python
    thread of the process ran), "device_s": of the rest, those inside a
    `.wait` and under no hiccup, beyond the median turn's wait (the process
    was fine and the device or its runtime was late)}; what is left of
    `excess_s` is the loop's thread alone losing the processor. None where
    the program does not record its turn from the inside (no second thread
    was there to own a pause)."""
    loop = load(rec)
    if loop is None or not loop["inside"]:
        return None
    turns = loop["turns"]
    limit = LONG_S + LONG_MEDIANS * statistics.median(
        t["t1"] - t["t0"] for t in turns)
    usual_wait = statistics.median(t["wait_s"] for t in turns)
    excess = host = device = 0.0
    for t in turns:
        over = t["t1"] - t["t0"] - limit
        if over <= 0:
            continue
        under = trace_reduce.clip(loop["hiccups"], t["t0"], t["t1"])
        h = min(over, trace_reduce.total(under))
        waited = trace_reduce.total(
            trace_reduce.subtract(t["waits"], under)) - usual_wait
        excess += over
        host += h
        device += min(over - h, max(0.0, waited))
    return {"window_s": loop["window_s"], "excess_s": excess,
            "host_s": host, "device_s": device}


def pause_share(rec: Dict, owner: str) -> Optional[float]:
    """`owner` ("host_s" | "device_s") of `pauses` over its `excess_s`; 0
    where the window held no long turn, because a metric that a cell lists
    has to be in every traced line of it and a pause is there by chance."""
    owned = pauses(rec)
    if owned is None:
        return None
    return owned[owner] / owned["excess_s"] if owned["excess_s"] > 0 else 0.0
