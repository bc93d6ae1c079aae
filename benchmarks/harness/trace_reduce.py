"""From the profiler's `.xplane.pb` to numbers: device busy/idle (union of
op intervals), time per op and per program, how much of the collectives'
time nothing else covers, and which host span owns each idle gap. Kept with
the benchmark, checked on a small recorded trace (tests/benchmarks), so that
every PR computes the same number in the same way.

A device plane is named `/device:TPU:<n>`; its line `XLA Ops` holds one
event per executed HLO op and `XLA Modules` one per executed program. Host
spans (`jax.profiler.TraceAnnotation`) are events on the thread lines of
`/host:CPU`, on the same clock. All arithmetic below is on plain
(start_s, end_s) intervals."""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
# control-flow wrappers span their bodies, whose ops are events of their own
WRAPPER = re.compile(r"^(while|conditional|call)([.\d_]|$)", re.I)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Interval], cover: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of (merged) `base` that (merged) `cover` does not cover."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        i = j
        while i < len(cover) and cover[i][0] < b:
            if cover[i][0] > cur:
                out.append((cur, cover[i][0]))
            cur = max(cur, cover[i][1])
            i += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def op_base(name: str) -> str:
    """`fusion.12` of the event name `%fusion.12 = f32[8]{0} fusion(...)`
    (the TPU's op events carry the whole HLO line)."""
    return name.split(" = ")[0].strip().lstrip("%")


def short_name(name: str) -> str:
    """A stable label for an op: its HLO name without the instance number,
    plus the shapes it produces without their layouts:
    `%fusion.7 = (f32[3072,768]{1,0:T(8,128)}, ...) fusion(...)` ->
    `fusion__f32_3072_768___...`. Instance numbers change with every
    recompile; kinds and shapes do not."""
    base = re.sub(r"[.\d]+$", "", op_base(name)) or op_base(name)
    if " = " not in name:
        return base
    rhs = re.sub(r"\{[^{}]*\}", "", name.split(" = ", 1)[1])
    head = rhs.split(") ")[0] if rhs.startswith("(") else rhs.split(" ")[0]
    shapes = "__".join(re.sub(r"[^A-Za-z0-9]+", "_", t).strip("_")
                       for t in re.findall(r"[a-z]+\d*\[[\d,]*\]", head))
    return (base + "__" + shapes)[:64] if shapes else base


def reduce_device(ops: Sequence[Tuple[str, float, float]], lo: float,
                  hi: float) -> Dict:
    """One device over the window [lo, hi): `ops` are (name, start, end)."""
    real = [(n, a, b) for n, a, b in ops if not WRAPPER.match(op_base(n))]
    is_coll = {n: bool(COLLECTIVE.search(op_base(n)))
               for n in {n for n, _, _ in real}}
    busy = clip(union((a, b) for _, a, b in real), lo, hi)
    coll = clip(union((a, b) for n, a, b in real if is_coll[n]), lo, hi)
    rest = clip(union((a, b) for n, a, b in real if not is_coll[n]), lo, hi)
    per_op = collections.Counter()
    for n, a, b in real:
        d = min(b, hi) - max(a, lo)
        if d > 0:
            per_op[n] += d
    return {"busy_s": total(busy), "window_s": hi - lo,
            "idle_gaps": subtract([(lo, hi)], busy),
            "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, rest)),
            "per_op": per_op}


def attribute_gaps(gaps: Sequence[Interval],
                   host: Sequence[Tuple[str, float, float]], other: str
                   ) -> Dict[str, Dict[str, float]]:
    """Each idle gap's seconds go to the host spans that overlap it (a
    span's share is its overlap); what no span covers goes to `other`."""
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"seconds": 0.0, "longest": 0.0})
    for ga, gb in gaps:
        covered = []
        for name, a, b in host:
            d = min(b, gb) - max(a, ga)
            if d > 0:
                out[name]["seconds"] += d
                out[name]["longest"] = max(out[name]["longest"], d)
                covered.append((max(a, ga), min(b, gb)))
        rest = total(subtract([(ga, gb)], union(covered)))
        if rest > 0:
            out[other]["seconds"] += rest
            out[other]["longest"] = max(out[other]["longest"], rest)
    return dict(out)


def attribute_innermost(gaps: Sequence[Interval],
                        host: Sequence[Tuple[str, float, float]],
                        other: str) -> Dict[str, Dict[str, float]]:
    """Each second of each idle gap goes to the INNERMOST host span that
    covers it (of the spans covering a moment, the one that started last;
    spans of one thread nest), what no span covers to `other`. The gaps'
    total is preserved, unlike attribute_gaps, which gives an overlap to
    every span that has it."""
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"seconds": 0.0, "longest": 0.0})
    host = sorted(host, key=lambda s: s[1])
    starts = [s[1] for s in host]
    longest = max((b - a for _, a, b in host), default=0.0)
    for ga, gb in gaps:
        # a span that reaches into the gap starts before the gap ends and
        # no earlier than the longest span before it begins
        inside = [s for s in host[bisect.bisect_left(starts, ga - longest):
                                  bisect.bisect_left(starts, gb)]
                  if s[2] > ga]
        cuts = sorted({ga, gb, *(min(max(t, ga), gb)
                                 for _, a, b in inside for t in (a, b))})
        runs: Dict[str, float] = collections.defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in inside if s[1] <= a and s[2] >= b]
            name = max(cover, key=lambda s: s[1])[0] if cover else other
            runs[name] += b - a
        for name, secs in runs.items():
            out[name]["seconds"] += secs
            out[name]["longest"] = max(out[name]["longest"], secs)
    return dict(out)


def load_xplane(path: str, host_spans: Sequence[str]) -> Dict:
    """{"devices": {index: {"ops": [(name, start_s, end_s)], "modules":
    [...]}}, "host": [(name, start_s, end_s)]} of one `.xplane.pb`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict] = {}
    host: List[Tuple[str, float, float]] = []
    wanted = set(host_spans)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(2)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend(
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name in wanted)
    return {"devices": devices, "host": host}


def reduce_loaded(loaded: Dict, other: str, innermost: bool = False
                  ) -> Dict:
    """The reduced trace the per-layer readers and `breakdown` read. The
    window is from the first to the last device op over all devices.
    `innermost`: the host spans nest (the engine loop's own), so each idle
    second goes to the innermost one; else to every span that overlaps."""
    devs = {i: d for i, d in loaded["devices"].items() if d["ops"]}
    if not devs:
        return {"devices_seen": 0}
    lo = min(a for d in devs.values() for _, a, _ in d["ops"])
    hi = max(b for d in devs.values() for _, _, b in d["ops"])
    per_dev = {i: reduce_device(d["ops"], lo, hi) for i, d in devs.items()}
    worst = max(per_dev, key=lambda i: per_dev[i]["window_s"]
                - per_dev[i]["busy_s"])
    ops = collections.Counter()
    for r in per_dev.values():
        for n, s in r["per_op"].items():
            ops[short_name(n)] += s / len(per_dev)
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    for d in devs.values():
        for n, a, b in d["modules"]:
            modules[re.sub(r"\(\d+\)$", "", n)].append(b - a)
    gaps = (attribute_innermost if innermost else attribute_gaps)(
        per_dev[worst]["idle_gaps"], loaded["host"], other)
    window = hi - lo
    return {
        "devices_seen": len(per_dev),
        "window_s": window,
        "busy_s": statistics.fmean(r["busy_s"] for r in per_dev.values()),
        "idle_share_worst": 1.0 - per_dev[worst]["busy_s"] / window,
        "collective_share_worst": max(
            r["collective_s"] for r in per_dev.values()) / window,
        "collective_exposed_share_worst": max(
            r["collective_exposed_s"] for r in per_dev.values()) / window,
        "device_ops": [[n, s] for n, s in ops.most_common(10)],
        "modules": {n: {"count": len(v), "total_s": sum(v),
                        "median_s": statistics.median(v)}
                    for n, v in modules.items()},
        "idle_gaps": sorted(
            ([n, g["seconds"]] for n, g in gaps.items()),
            key=lambda x: -x[1])[:6] + sorted(
            ([n + ".longest", g["longest"]] for n, g in gaps.items()),
            key=lambda x: -x[1])[:4],
    }


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def profile_options():
    """Device ops and TraceAnnotations; no Python call tracing (it slows
    the threads that feed the chip and bloats the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
