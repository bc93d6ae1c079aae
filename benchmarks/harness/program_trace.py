"""What the benchmark reads of the PROGRAM's own tracing (PR 24): the spans
and record lists of `paddle_tpu.observability.tracing`, and the layer scopes
(`jax.named_scope`) its device programs carry.

- `collect()` takes the recorded spans, step records and request records out
  of the program's store; a program without that store (an older commit)
  gives None and every reader of it leaves its metric out.
- `reduce_scopes()` sums device time by layer scope. The scope of an op is
  the innermost of `SCOPES` in its `op_name` (backward ops carry it inside
  `transpose(jvp(...))`), which the profiler keeps as the stat `tf_op` of the
  event's metadata. `jax.profiler.ProfileData` does not show event metadata,
  so the few fields needed are read from the `.xplane.pb` wire format here.

Names of spans, record fields and scopes are the yardstick's: PERF.md
section 3 lists them, tests/test_layer_scopes.py and tests/test_decode_spans.py
hold the program to them."""

from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

from . import trace_reduce

# innermost first where they nest: an op under layers/attention is attention's
SCOPES = ("ln", "qkv", "kv_write", "kv_gather", "attention", "proj", "mlp",
          "mlm_head", "nsp_head", "loss", "clip", "optimizer", "embed",
          "head", "layers")
# scopes whose ops compute the model; the rest of a decode program moves the
# KV pool (kv_write, kv_gather, layers.carry) or is unscoped
COMPUTE = ("ln", "qkv", "attention", "proj", "mlp", "head", "embed")
# a lax.scan slices its stacked operands and writes its stacked outputs back
# with ops named .../while/body/dynamic_slice | dynamic_update_slice: in the
# serving programs that is the per-layer slice of the KV pools (and weights)
CARRY = re.compile(r"while/body/dynamic_(update_)?slice$")
# a path component that is a scope, bare or inside autodiff's wrappers:
# `mlp`, `jvp(layers)`, `transpose(jvp(mlm_head))`; `jit(loss)` is a call
_SCOPE = re.compile(r"^(?:(?:transpose|jvp|vmap|checkpoint|remat)\()*"
                    r"([A-Za-z_][A-Za-z0-9_.]*)\)*$")

Span = Tuple[str, float, float, int, Dict]   # name, t0, t1, thread, facts


def scope_of(op_name: Optional[str]) -> str:
    """`jit(f)/jit(main)/layers/while/body/closed_call/qkv/dot_general` ->
    `qkv`; `.../transpose(jvp(layers))/transpose(jvp(mlp))/mul` -> `mlp`;
    under `layers` alone -> `layers.carry` (the scan's slices) or
    `layers.other`; no op_name or none of SCOPES in it -> `unscoped`."""
    if not op_name:
        return "unscoped"
    path = op_name.rstrip(":").split("/")
    for part in reversed(path[:-1]):        # the last part is the primitive
        m = _SCOPE.match(part)
        if m and m.group(1) in SCOPES:
            if m.group(1) != "layers":
                return m.group(1)
            return "layers.carry" if CARRY.search(op_name.rstrip(":")) \
                else "layers.other"
    return "unscoped"


# -- the program's spans and records ---------------------------------------


def collect(w0: float, w1: float) -> Optional[Dict]:
    """The program's recorded spans that END in [w0, w1) and its step and
    request records whose time lies there (CLOCK_MONOTONIC seconds), or
    None where the program keeps no such store."""
    try:
        from paddle_tpu.observability import tracing
        spans, steps, requests = (
            tracing.get_spans(), tracing.get_records("decode.steps"),
            tracing.get_records("decode.requests"))
    except (ImportError, AttributeError):
        return None
    rows = [(s.name, s.ts, s.ts + s.dur, s.tid, dict(s.args or {}))
            for s in spans if w0 <= s.ts + s.dur < w1]
    return {"spans": rows,
            "steps": [r for r in steps if w0 <= r["t"] < w1],
            "requests": [r for r in requests if w0 <= r["t_finish"] < w1],
            "window": (w0, w1), "dropped": tracing.dropped_spans()}


def span_seconds(spans: Iterable[Span], name: str, lo: float, hi: float
                 ) -> float:
    return sum(min(b, hi) - max(a, lo) for n, a, b, _, _ in spans
               if n == name and min(b, hi) > max(a, lo))


def loop_host_seconds(spans: Sequence[Span], lo: float, hi: float) -> float:
    """Seconds of [lo, hi) the engine loop spent inside `decode.turn` and
    outside its waits for the device (`decode.*.wait`): the Python of the
    loop."""
    turns = trace_reduce.clip(trace_reduce.union(
        (a, b) for n, a, b, _, _ in spans if n == "decode.turn"), lo, hi)
    waits = trace_reduce.union(
        (a, b) for n, a, b, _, _ in spans
        if n.startswith("decode.") and n.endswith(".wait"))
    return trace_reduce.total(trace_reduce.subtract(turns, waits))


# -- the .xplane.pb wire format, as far as the scopes need it --------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, val


def _map_entries(plane: bytes, field: int):
    for f, entry in _fields(plane):
        if f == field:
            yield next(v for g, v in _fields(entry) if g == 2)


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def load_scoped_ops(path: str) -> Dict[int, Dict]:
    """{device index: {"ops": [(name, op_name, program_id, start_s, end_s)],
    "modules": {program_id: module name}}} of one `.xplane.pb`."""
    with open(path, "rb") as f:
        space = f.read()
    devices: Dict[int, Dict] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name = next((v.decode() for g, v in _fields(plane) if g == 2), "")
        m = trace_reduce.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_names = {}
        for sm in _map_entries(plane, 5):
            d = dict(_fields(sm))
            stat_names[d.get(1, 0)] = d.get(2, b"").decode()
        meta = {}
        for em in _map_entries(plane, 4):
            row = {"name": "", "tf_op": None, "program_id": None}
            mid = 0
            for g, v in _fields(em):
                if g == 1:
                    mid = v
                elif g == 2:
                    row["name"] = v.decode()
                elif g == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1))
                    if key == "tf_op":
                        row["tf_op"] = st[5].decode() if 5 in st \
                            else stat_names.get(st.get(7))
                    elif key == "program_id":
                        row["program_id"] = st.get(3, st.get(4))
            meta[mid] = row
        dev = devices.setdefault(int(m.group(2)), {"ops": [], "modules": {}})
        for g, line in _fields(plane):
            if g != 3:
                continue
            head = {h: v for h, v in _fields(line) if h in (2, 3)}
            kind = head.get(2, b"").decode()
            if kind not in ("XLA Ops", "XLA Modules"):
                continue
            t_line = head.get(3, 0) * 1e-9
            for h, ev in _fields(line):
                if h != 4:
                    continue
                e = dict((k, v) for k, v in _fields(ev) if k in (1, 2, 3))
                row = meta.get(e.get(1, 0))
                if row is None:
                    continue
                a = t_line + _signed(e.get(2, 0)) * 1e-12
                b = a + e.get(3, 0) * 1e-12
                if kind == "XLA Ops":
                    dev["ops"].append((row["name"], row["tf_op"],
                                       row["program_id"], a, b))
                else:
                    pid = re.search(r"\((\d+)\)$", row["name"])
                    if pid:
                        dev["modules"][int(pid.group(1))] = re.sub(
                            r"\(\d+\)$", "", row["name"])
    return devices


def reduce_scopes(path: str) -> Dict:
    """Device time by layer scope, the mean over the devices of the trace:
    {"busy_s", "by_scope": {scope: s}, "programs": {module: {"total_s",
    "by_scope"}}, "scoped_ops", "unscoped_top": [[op label, s], ...]}.
    Control-flow wrappers are left out as in trace_reduce (their bodies'
    ops are events of their own), so a scope's seconds are its ops' own."""
    devices = {i: d for i, d in load_scoped_ops(path).items() if d["ops"]}
    if not devices:
        return {"devices_seen": 0}
    n = len(devices)
    by_scope: Dict[str, float] = collections.defaultdict(float)
    programs: Dict[str, Dict] = {}
    unscoped: Dict[str, float] = collections.defaultdict(float)
    busy = 0.0
    scoped_ops = 0
    for d in devices.values():
        real = [op for op in d["ops"]
                if not trace_reduce.WRAPPER.match(
                    trace_reduce.op_base(op[0]))]
        busy += trace_reduce.total(trace_reduce.union(
            (a, b) for _, _, _, a, b in real)) / n
        for name, op_name, pid, a, b in real:
            scope = scope_of(op_name)
            by_scope[scope] += (b - a) / n
            prog = programs.setdefault(
                d["modules"].get(pid, str(pid)),
                {"total_s": 0.0, "by_scope": collections.defaultdict(float)})
            prog["total_s"] += (b - a) / n
            prog["by_scope"][scope] += (b - a) / n
            if scope == "unscoped":
                unscoped[trace_reduce.short_name(name)] += (b - a) / n
            else:
                scoped_ops += 1
    return {"devices_seen": n, "busy_s": busy, "by_scope": dict(by_scope),
            "programs": {k: {"total_s": v["total_s"],
                             "by_scope": dict(v["by_scope"])}
                         for k, v in programs.items()},
            "scoped_ops": scoped_ops,
            "unscoped_top": sorted(([k, v] for k, v in unscoped.items()),
                                   key=lambda x: -x[1])[:8]}


def device_scopes(rec: Dict) -> Optional[Dict]:
    """The scope reduction a traced run's runner put into its records
    (`rec["scopes"]`), or None: untraced, or a recorded trace in which no
    device op carries a scope."""
    scopes = rec.get("scopes") if rec.get("trace") else None
    return scopes if scopes and scopes.get("scoped_ops") else None
