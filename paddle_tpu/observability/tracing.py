"""Unified span store + chrome-trace export + distributed trace context.

One process-wide span list replaces the profiler's ad-hoc `_host_spans`:
`profiler.RecordEvent` host spans (cat="host"), executor/trainer/SPMD step
telemetry (cat="step"), and any other subsystem annotation all land here,
and `export_trace` merges them with the jax.profiler device timeline
(the `*.trace.json.gz` chrome traces jax writes under
`plugins/profile/<run>/`) into ONE chrome://tracing / perfetto-loadable
JSON file — the role of the reference's profiler.proto + tools/timeline.py
converter.

Timestamps: span ts/dur are time.perf_counter() seconds (matching what
RecordEvent always recorded); exported values are microseconds. Device
events keep their own profiler epoch — perfetto renders them as separate
tracks, which is how the reference timeline showed host vs. CUPTI streams
too.

Distributed tracing (PROFILE.md §Distributed tracing): a `TraceContext`
(trace_id, span_id, parent_span_id, sampled — the W3C `traceparent` wire
format) rides a contextvar in-process, HTTP headers across the serving
tier (`begin_request`/`trace_headers`), and the PS RPC envelope
(ps/protocol.py TRACE_FIELD) across the parameter-server tier. Sampling
is head-based: the process that STARTS a trace rolls
`PADDLE_TPU_TRACE_SAMPLE` (0.0..1.0, default 0 = off) once; every
downstream hop honors the propagated flag, so a request is either traced
end-to-end or costs nothing anywhere. Sampled spans are tagged into the
in-memory ring (args trace_id/span_id/parent_span_id) AND persisted to a
per-process JSONL sink under `PADDLE_TPU_TRACE_DIR` (atomic whole-file
rewrites via resilience/atomic.py, so a concurrent reader never sees a
torn line); `tools/obsdump.py trace DIR --trace-id ID` reassembles the
cross-process tree. This module stays stdlib-only (obsdump imports it by
file path); resilience.atomic loads lazily inside the writers.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import contextvars
import glob
import gzip
import itertools
import json
import os
import random
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

__all__ = ["Span", "span", "record_span", "get_spans", "clear_spans",
           "dropped_spans", "save_spans", "export_trace",
           "OpenSpan", "open_span", "record", "current_span",
           "start_recording", "stop_recording", "recorded",
           "add_record", "get_records", "kept_rows",
           "HICCUP_TICK_S", "HICCUP_LATE_S",
           "set_annotation_provider",
           "process_start", "boot_span", "compile_event",
           "compile_duration", "last_compile_request", "boot_summary",
           "merge_chrome_traces",
           "TraceContext", "parse_traceparent", "sample_rate",
           "start_trace", "current_trace", "current_trace_id",
           "activate", "begin_request", "trace_headers",
           "response_headers", "trace_span", "step_span",
           "record_span_ctx", "record_trace_span", "flush_trace_sink",
           "sink_path", "read_trace_dir", "build_trace_tree",
           "trace_summaries", "trace_records_to_chrome"]

# Bound host memory: a week-long trainer recording a span per step must
# not OOM the host. The store is a ring — the OLDEST spans are evicted
# (and counted in dropped_spans()), so profiling a late window of a long
# run still exports that window rather than stale day-one spans.
MAX_SPANS = 200_000


class Span(NamedTuple):
    name: str
    ts: float            # perf_counter seconds
    dur: float           # seconds
    cat: str             # "host" | "step" | subsystem-chosen
    tid: int             # recording thread ident
    args: Optional[Dict[str, Any]]


_lock = threading.Lock()
_spans: "collections.deque[Span]" = collections.deque()
_dropped = 0


def record_span(name: str, ts: float, dur: float, cat: str = "host",
                args: Optional[Dict[str, Any]] = None):
    global _dropped
    sp = Span(name, ts, dur, cat, threading.get_ident(), args)
    with _lock:
        _spans.append(sp)
        while len(_spans) > MAX_SPANS:
            _spans.popleft()
            _dropped += 1


# ---------------------------------------------------------------------------
# Distributed trace context (W3C traceparent)
# ---------------------------------------------------------------------------

TRACE_DIR_ENV = "PADDLE_TPU_TRACE_DIR"
TRACE_SAMPLE_ENV = "PADDLE_TPU_TRACE_SAMPLE"

# sampling decisions use a dedicated RNG so tests can seed it without
# perturbing anything else's randomness
_sample_rng = random.Random()


class TraceContext(NamedTuple):
    """One hop of a distributed trace: ids are lower-hex strings in the
    W3C trace-context widths (trace_id 32, span_id 16). `sampled` is the
    head-based decision made where the trace STARTED — downstream hops
    copy it from the wire instead of re-rolling, so one request is
    traced end-to-end or not at all."""

    trace_id: str
    span_id: str
    parent_span_id: Optional[str] = None
    sampled: bool = False

    def header(self) -> str:
        """W3C `traceparent`: 00-<trace_id>-<span_id>-<flags>."""
        return (f"00-{self.trace_id}-{self.span_id}-"
                f"{'01' if self.sampled else '00'}")

    def child(self) -> "TraceContext":
        """Fresh span id, this span as parent, same trace + decision."""
        return TraceContext(self.trace_id, _new_span_id(),
                            self.span_id, self.sampled)


def _new_trace_id() -> str:
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


def parse_traceparent(value: Optional[str]) -> Optional[TraceContext]:
    """Parse a `traceparent` header; None on anything malformed (an
    unparseable header means "start a fresh trace", never an error)."""
    if not value or not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16 \
            or len(flags) != 2:
        return None
    try:
        int(trace_id, 16), int(span_id, 16), int(flags, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id.lower(), span_id.lower(), None,
                        bool(int(flags, 16) & 0x01))


def sample_rate() -> float:
    """Head-sampling probability from PADDLE_TPU_TRACE_SAMPLE (clamped
    to [0, 1]; unset/malformed = 0 = tracing off). Re-read per call so
    an operator can flip it live."""
    raw = os.environ.get(TRACE_SAMPLE_ENV)
    if not raw:
        return 0.0
    try:
        return min(1.0, max(0.0, float(raw)))
    except ValueError:
        return 0.0


def start_trace(sampled: Optional[bool] = None) -> TraceContext:
    """Mint a new root context. sampled=None rolls `sample_rate()`
    once — the head-based decision every downstream hop inherits."""
    if sampled is None:
        rate = sample_rate()
        sampled = rate > 0.0 and _sample_rng.random() < rate
    return TraceContext(_new_trace_id(), _new_span_id(), None,
                        bool(sampled))


_current: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("paddle_tpu_trace", default=None)


def current_trace() -> Optional[TraceContext]:
    return _current.get()


def current_trace_id() -> Optional[str]:
    """trace_id of the active SAMPLED context (None otherwise) — the
    event log's join key (events.py set_trace_provider)."""
    cur = _current.get()
    return cur.trace_id if cur is not None and cur.sampled else None


@contextlib.contextmanager
def activate(ctx: Optional[TraceContext]):
    """Make `ctx` the ambient context for the with-body (any thread)."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def begin_request(headers) -> TraceContext:
    """Extract-or-start at a service edge: adopt the caller's
    `traceparent` (including its sampling decision) or mint a fresh
    root sampled by PADDLE_TPU_TRACE_SAMPLE. Always returns a context —
    the trace_id doubles as the X-Request-Id response header even for
    unsampled requests. `headers` is any .get()-able mapping (the
    stdlib handler's email.message.Message included)."""
    ctx = parse_traceparent(headers.get("traceparent")
                            if headers is not None else None)
    return ctx if ctx is not None else start_trace()


def trace_headers(ctx: Optional[TraceContext] = None) -> Dict[str, str]:
    """Outbound propagation headers for a downstream HTTP call ({} when
    no context is active). Unsampled contexts propagate too — the
    sampling decision was made at the head, and a downstream hop must
    not re-roll it."""
    cur = _current.get() if ctx is None else ctx
    if cur is None:
        return {}
    return {"traceparent": cur.header()}


def response_headers(ctx: Optional[TraceContext]) -> Dict[str, str]:
    """Reply headers every /v1/* response carries (SERVING.md §HTTP
    API): the request id for log correlation plus the traceparent so
    clients can read the ids + sampling decision back."""
    if ctx is None:
        return {}
    return {"X-Request-Id": ctx.trace_id, "traceparent": ctx.header()}


# -- per-process JSONL sink -------------------------------------------------

# The sink is SEGMENTED: each segment file is published as atomic
# whole-file rewrites (readers never see a torn line) and sealed once it
# reaches _SINK_SEGMENT_SPANS, after which a fresh trace-<pid>-<rand>
# file starts — so both the in-memory buffer and the per-flush rewrite
# cost stay bounded (amortized O(1) I/O per span) no matter how long a
# sampled process lives. read_trace_dir globs every segment.
_SINK_SEGMENT_SPANS = 4096
MAX_SINK_SPANS = 100_000   # backstop drop-oldest; unreachable with
# segmenting unless rolling keeps failing (unwritable dir)
_SINK_FLUSH_EVERY_S = 0.25
_SINK_FLUSH_EVERY_N = 256

_sink_lock = threading.Lock()        # buffer/bookkeeping access
_sink_flush_lock = threading.Lock()  # serializes writers: file content
# must never go backwards (an older snapshot landing after a newer one
# would silently drop the tail spans)
_sink_state = {"dir": None, "path": None, "pid": None,
               "lines": [], "flushed": 0, "last_flush": 0.0,
               "atexit": False}


def sink_path() -> Optional[str]:
    """Resolved sink file for THIS process, or None when
    PADDLE_TPU_TRACE_DIR is unset."""
    with _sink_lock:
        if _sink_state["dir"] != os.environ.get(TRACE_DIR_ENV) \
                or _sink_state["pid"] != os.getpid():
            return _sink_reset_locked()
        return _sink_state["path"]


def _sink_reset_locked() -> Optional[str]:
    d = os.environ.get(TRACE_DIR_ENV)
    _sink_state.update(dir=d, pid=os.getpid(), lines=[], flushed=0,
                       last_flush=0.0)
    _sink_state["path"] = None if not d else os.path.join(
        d, f"trace-{os.getpid()}-{os.urandom(4).hex()}.jsonl")
    return _sink_state["path"]


def _sink_append(rec: Dict[str, Any]):
    line = json.dumps(rec, default=str) + "\n"
    flush_now = roll_now = False
    with _sink_lock:
        if _sink_state["dir"] != os.environ.get(TRACE_DIR_ENV) \
                or _sink_state["pid"] != os.getpid():
            _sink_reset_locked()
        if _sink_state["path"] is None:
            return
        lines = _sink_state["lines"]
        lines.append(line)
        if len(lines) > MAX_SINK_SPANS:
            del lines[:len(lines) - MAX_SINK_SPANS]
            _sink_state["flushed"] = 0  # prefix changed: rewrite all
        if not _sink_state["atexit"]:
            _sink_state["atexit"] = True
            atexit.register(flush_trace_sink)
        now = time.monotonic()
        pending = len(lines) - _sink_state["flushed"]
        roll_now = len(lines) >= _SINK_SEGMENT_SPANS
        flush_now = pending >= _SINK_FLUSH_EVERY_N or \
            (pending > 0 and now - _sink_state["last_flush"]
             >= _SINK_FLUSH_EVERY_S)
    if roll_now:
        _sink_roll()
    elif flush_now:
        flush_trace_sink()


def _sink_write(path: str, lines: List[str]) -> bool:
    from ..resilience.atomic import write_text

    try:
        write_text(path, "".join(lines))
        return True
    except OSError:
        return False  # full disk etc: keep buffering, retry next flush


def flush_trace_sink():
    """Publish every buffered sampled span to the per-process sink
    segment (one atomic whole-file rewrite — a concurrent obsdump
    reassembly never reads a torn line). No-op without
    PADDLE_TPU_TRACE_DIR. Writers are serialized and `flushed` only
    advances AFTER a successful write: a failed write (or a racing
    older snapshot) can never strand tail spans as flushed-but-absent,
    so the atexit flush still publishes them."""
    with _sink_flush_lock:
        with _sink_lock:
            path = _sink_state["path"]
            lines = list(_sink_state["lines"])
            if path is None or len(lines) == _sink_state["flushed"]:
                return
        if not _sink_write(path, lines):
            return
        with _sink_lock:
            if _sink_state["path"] == path \
                    and _sink_state["flushed"] < len(lines):
                _sink_state["flushed"] = len(lines)
                _sink_state["last_flush"] = time.monotonic()


def _sink_roll():
    """Seal the current segment (final full write) and start a fresh
    trace-<pid>-<rand> file — the per-flush rewrite cost and the buffer
    are both bounded by _SINK_SEGMENT_SPANS. Spans appended while the
    seal was being written stay buffered for the new segment."""
    with _sink_flush_lock:
        with _sink_lock:
            path = _sink_state["path"]
            lines = list(_sink_state["lines"])
        if path is None or not lines:
            return
        if not _sink_write(path, lines):
            return  # unwritable: keep the segment open, retry later
        with _sink_lock:
            if _sink_state["path"] != path:
                return  # env/pid reset raced us; nothing to seal
            del _sink_state["lines"][:len(lines)]
            _sink_state["flushed"] = 0
            _sink_state["last_flush"] = time.monotonic()
            _sink_state["path"] = os.path.join(
                os.path.dirname(path),
                f"trace-{os.getpid()}-{os.urandom(4).hex()}.jsonl")


def record_span_ctx(ctx: Optional[TraceContext], name: str, dur: float,
                    cat: str = "trace", t0_perf: Optional[float] = None,
                    **args):
    """Record `ctx` itself as one finished span: tagged into the ring
    AND appended to the JSONL sink. No-op unless ctx is sampled — the
    zero-overhead contract for unsampled requests."""
    if ctx is None or not ctx.sampled:
        return
    t0 = time.perf_counter() - dur if t0_perf is None else t0_perf
    tagged = dict(args)
    tagged["trace_id"] = ctx.trace_id
    tagged["span_id"] = ctx.span_id
    if ctx.parent_span_id:
        tagged["parent_span_id"] = ctx.parent_span_id
    record_span(name, t0, dur, cat, tagged)
    _sink_append({
        "trace_id": ctx.trace_id, "span_id": ctx.span_id,
        "parent_span_id": ctx.parent_span_id, "name": name, "cat": cat,
        "ts": time.time() - dur, "dur": dur, "pid": os.getpid(),
        "tid": threading.get_ident(),
        "args": args or None})


def record_trace_span(name: str, parent: Optional[TraceContext],
                      dur: float, cat: str = "trace",
                      t0_perf: Optional[float] = None, **args
                      ) -> Optional[TraceContext]:
    """Mint a child of `parent` and record it retroactively (the
    batcher/decode scheduler shape: the span's duration is only known
    after the fact). Returns the child, or None when unsampled."""
    if parent is None or not parent.sampled:
        return None
    child = parent.child()
    record_span_ctx(child, name, dur, cat=cat, t0_perf=t0_perf, **args)
    return child


@contextlib.contextmanager
def trace_span(name: str, cat: str = "trace",
               ctx: Optional[TraceContext] = None, **args):
    """Span that participates in the distributed trace: mints a child
    of the ambient (or explicit `ctx`) context, makes it ambient for
    the body — nested spans and downstream propagation see it — and
    records it on exit. When no sampled context is active this is a
    near-free no-op (one contextvar read), yielding the unchanged
    context. Pass `ctx` explicitly to adopt a context captured on
    another thread (batcher lead request, PS server envelope)."""
    cur = ctx if ctx is not None else _current.get()
    if cur is None or not cur.sampled:
        yield cur
        return
    child = cur.child()
    token = _current.set(child)
    t0 = time.perf_counter()
    try:
        yield child
    finally:
        _current.reset(token)
        record_span_ctx(child, name, time.perf_counter() - t0,
                        cat=cat, t0_perf=t0, **args)


@contextlib.contextmanager
def span(name: str, cat: str = "host", **args):
    """Context-manager form of `open_span` for coarse sites (a training
    step, a compiled program's run): always kept in the unified store,
    whether or not recording is on. When a sampled trace context is
    active, the span additionally joins the distributed trace (child ids
    + JSONL sink) — the executor's step spans gain the active trace id
    through exactly this path."""
    sp = open_span(name, cat, ctx=_current.get(), keep=True)
    try:
        yield
    finally:
        sp.close(**args)


@contextlib.contextmanager
def step_span(name: str, cat: str = "step", **args):
    """`span()` that also STARTS a root trace when none is active and
    PADDLE_TPU_TRACE_SAMPLE is armed — the training path's trace
    origin: Executor.run / run_chained / run_stream windows wrap their
    dispatch in this, so PS RPCs issued inside the step inherit the
    step's trace id without any trainer changes."""
    token = None
    if _current.get() is None and sample_rate() > 0.0:
        token = _current.set(start_trace())
    try:
        with span(name, cat=cat, **args):
            yield
    finally:
        if token is not None:
            _current.reset(token)


# ---------------------------------------------------------------------------
# Recorded spans: the program's own instrumentation of its hot loops
# ---------------------------------------------------------------------------
#
# One primitive, `open_span()` ... `.close()`, serves the benchmark (the
# ring, read after a run), the operator (a `jax.profiler.TraceAnnotation`
# of the same name while it is open, so that in a profiler trace the span
# lies on `/host:CPU`, on the device trace's clock) and the distributed
# trace (a child of the request's sampled `TraceContext`, into the sink).
# A hot-loop site is written
#
#     sp = _tracing.open_span("decode.dispatch", "decode") \
#         if _tracing.recording else None
#     ...
#     if sp is not None:
#         sp.close(slots=C)
#
# so that with recording off (the default) it costs one branch on this
# module's flag: no clock read, no allocation, no lock. Request-level
# sites add `or req.traced` (the request's context is sampled), which is
# how the old `record_trace_span` names stay in `obsdump trace`.
#
# Times are CLOCK_MONOTONIC seconds (`time.monotonic`; on Linux
# `perf_counter` reads the same clock, so RecordEvent's spans share the
# axis), the clock the serving code stamps its requests with.

recording = False
clock = time.monotonic
_IMPORT_STAMP = clock()
MAX_RECORDS = 200_000
# record lists that are the PROCESS's, not a recording's: always on, short
# (a boot makes tens of rows), and left alone by `clear_spans()`
KEPT_RECORDS = {"compile.requests": 4096, "boot.spans": 256}
_kept_rows = 0

# the recording's hiccup thread wakes every HICCUP_TICK_S and writes a row
# of `host.hiccups` when it wakes more than HICCUP_LATE_S after it meant to
HICCUP_TICK_S = 0.02
HICCUP_LATE_S = 0.05

_annotation_provider = None     # () -> jax.profiler.TraceAnnotation
_annotation = None              # resolved by start_recording()
_hiccups = None                 # (thread, its stop event) of the recording
_stack = threading.local()
_next_sid = itertools.count(1)
_records: Dict[str, "collections.deque"] = {}


def set_annotation_provider(fn):
    """Inject where `TraceAnnotation` comes from (observability/__init__
    wires `jax.profiler`), so that this file imports no JAX."""
    global _annotation_provider, _annotation
    _annotation_provider, _annotation = fn, None


def start_recording(clear: bool = True):
    """Turn the program's span sites and record lists on (a `--trace 1`
    benchmark run, a `POST /v1/profile` capture); `clear` empties the
    ring and the record lists first. The recording owns ONE hiccup thread
    (`_watch_hiccups`), however often it is started."""
    global recording, _annotation, _hiccups
    if clear:
        clear_spans()
    if _annotation is None and _annotation_provider is not None:
        _annotation = _annotation_provider()
    with _lock:
        if _hiccups is None:
            stop = threading.Event()
            _hiccups = (threading.Thread(
                target=_watch_hiccups, args=(stop,),
                name="paddle-tpu-hiccups", daemon=True), stop)
            _hiccups[0].start()
    recording = True


def stop_recording():
    global recording, _hiccups
    recording = False
    with _lock:
        watch, _hiccups = _hiccups, None
    if watch is not None:
        watch[1].set()
        watch[0].join(timeout=1.0)


def _watch_hiccups(stop: threading.Event, sleep=None, now=None):
    """The body of the recording's hiccup thread: sleep HICCUP_TICK_S at a
    time on `clock` and, on a wake more than HICCUP_LATE_S after the one
    meant, append `{"t": the wake meant, "late_s": how late}` to the
    record list `host.hiccups`. It does nothing else and holds no lock
    while it sleeps, so a late wake means that NO Python thread of this
    process ran for that long (the host, the hypervisor, a collector, a C
    call that kept the interpreter lock): a long `decode.resolve.wait`
    with no row beside it was the device's or its runtime's. `sleep` and
    `now` are the tests' (a made-up late wake)."""
    sleep = stop.wait if sleep is None else sleep
    now = clock if now is None else now
    due = now() + HICCUP_TICK_S
    while not stop.is_set():
        sleep(max(0.0, due - now()))
        woke = now()
        if woke - due > HICCUP_LATE_S and not stop.is_set():
            add_record("host.hiccups", {"t": due, "late_s": woke - due})
        due = woke + HICCUP_TICK_S


@contextlib.contextmanager
def recorded(clear: bool = True):
    """`with tracing.recorded():` records for the body; a recording that
    was already on is left on."""
    was = recording
    start_recording(clear=clear and not was)
    try:
        yield
    finally:
        if not was:
            stop_recording()


class OpenSpan:
    """A span between `open_span()` and `close()`. `sid` names it in the
    `parent` field of what it causes; `rid` is the request it serves."""

    __slots__ = ("name", "cat", "sid", "parent", "rid", "t0", "ctx",
                 "_token", "_ann", "_keep")

    def close(self, **facts) -> float:
        t1 = clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = getattr(_stack, "spans", None)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:   # an exception skipped the close
            del stack[stack.index(self):]   # of a child
        facts["sid"] = self.sid
        if self.parent is not None:
            facts["parent"] = self.parent
        if self.rid is not None:
            facts["rid"] = self.rid
        if self._token is not None:
            _current.reset(self._token)
            record_span_ctx(self.ctx, self.name, t1 - self.t0,
                            cat=self.cat, t0_perf=self.t0, **facts)
        elif self._keep:
            record_span(self.name, self.t0, t1 - self.t0, self.cat, facts)
        return t1


def _parent_sid(parent) -> Optional[int]:
    return parent.sid if isinstance(parent, OpenSpan) else parent


def open_span(name: str, cat: str = "host", parent=None, rid=None,
              ctx: Optional[TraceContext] = None, keep: bool = False
              ) -> OpenSpan:
    """Open a span on this thread. `parent` (an OpenSpan or its `sid`)
    defaults to the span open on this thread, `rid` to that span's.
    With a sampled `ctx` the span is a child of it in the distributed
    trace and the ambient context while it is open, whether or not
    recording is on; `keep` puts it into the ring either way."""
    sp = OpenSpan()
    stack = getattr(_stack, "spans", None)
    if stack is None:
        stack = _stack.spans = []
    top = stack[-1] if stack else None
    sp.name, sp.cat, sp.sid = name, cat, next(_next_sid)
    sp.parent = _parent_sid(parent) if parent is not None \
        else (top.sid if top is not None else None)
    sp.rid = rid if rid is not None or top is None else top.rid
    sp._keep = recording or keep
    sp.ctx = sp._token = sp._ann = None
    if ctx is not None and ctx.sampled:
        sp.ctx = ctx.child()
        sp._token = _current.set(sp.ctx)
    if recording and _annotation is not None:
        sp._ann = _annotation(name)
        sp._ann.__enter__()
    stack.append(sp)
    sp.t0 = clock()
    return sp


def current_span() -> Optional[OpenSpan]:
    """The innermost span open on this thread (a request captures it as
    the parent of the spans other threads record for it)."""
    stack = getattr(_stack, "spans", None)
    return stack[-1] if stack else None


def record(name: str, t0: float, t1: float, cat: str = "host",
           parent=None, rid=None, ctx: Optional[TraceContext] = None,
           **facts):
    """A span whose ends are known after the fact (a request's queue
    wait, its time to the first token): into the ring when recording,
    into the distributed trace when `ctx` is sampled."""
    facts["sid"] = next(_next_sid)
    if parent is not None:
        facts["parent"] = _parent_sid(parent)
    if rid is not None:
        facts["rid"] = rid
    if ctx is not None and ctx.sampled:
        record_span_ctx(ctx.child(), name, t1 - t0, cat=cat, t0_perf=t0,
                        **facts)
    elif recording:
        record_span(name, t0, t1 - t0, cat, facts)


def add_record(kind: str, row: Dict[str, Any]):
    """Append `row` to the record list `kind` (the decode engine's
    `decode.steps` and `decode.requests`), kept with the spans, bounded
    like them, read with `get_records` when a run ends."""
    global _kept_rows
    with _lock:
        rows = _records.get(kind)
        if rows is None:
            rows = _records[kind] = collections.deque(
                maxlen=KEPT_RECORDS.get(kind, MAX_RECORDS))
        rows.append(row)
        if kind in KEPT_RECORDS:
            _kept_rows += 1


def kept_rows() -> int:
    """How many rows the kept lists have taken so far: a view computed
    from them (a polled `status()["boot"]`) is made anew only when this
    has risen."""
    return _kept_rows


def get_records(kind: str) -> List[Dict[str, Any]]:
    with _lock:
        return list(_records.get(kind, ()))


def get_spans(cat: Optional[str] = None) -> List[Span]:
    with _lock:
        out = list(_spans)
    if cat is not None:
        out = [s for s in out if s.cat == cat]
    return out


def dropped_spans() -> int:
    with _lock:
        return _dropped


def clear_spans():
    global _dropped
    with _lock:
        _spans.clear()
        for kind in [k for k in _records if k not in KEPT_RECORDS]:
            del _records[kind]
        _dropped = 0


# ---------------------------------------------------------------------------
# A boot, from the inside: the process's start, one row a compile request
# ---------------------------------------------------------------------------
#
# JAX reports every compile request through `jax.monitoring`, on the thread
# that makes it: `jaxpr_trace_duration` once for every nested jitted
# function and last for the outermost, `jaxpr_to_mlir_module_duration`
# (inside which inner functions are traced and reported again), then the
# persistent cache's `compile_requests_use_cache`, `cache_hits` (+
# `compile_time_saved_sec`, `cache_retrieval_time_sec`) or `cache_misses`,
# and `backend_compile_duration`, which closes the request, on a hit too.
# `observability/__init__` registers `compile_event` / `compile_duration`
# as the listeners (this file imports no JAX); they join the events of one
# thread into one row of `compile.requests` when the last one arrives. The
# rows are always on: a process makes tens of them, and one inside a served
# window is a fault that wants a name.

_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_BACKEND = "/jax/core/compile/backend_compile_duration"
_JAX_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# `cache_misses` comes with the entry a miss writes, so that the next
# process hits; a request that the cache was not asked about, or asked
# with no directory to look in, or whose program it does not keep (under
# JAX's thresholds of size and compile time) is "off": compiled every boot
_JAX_CACHE = {"/jax/compilation_cache/cache_misses": "miss",
              "/jax/compilation_cache/cache_hits": "hit"}

# the most a request's own trace may end before its lowering begins: what
# JAX does between the two grows with the program (the largest read on the
# chip: 0.05 s, a training step traced for 1.7 s; PERF.md section 6, PR 37)
TRACE_JOIN_GAP_S = 1.0

_process_start: Optional[float] = None


def process_start() -> float:
    """This process's start on `clock`: the anchor a boot's parts are
    measured from. The kernel's own stamp (`/proc/self/stat` field 22, on
    CLOCK_BOOTTIME, brought over to CLOCK_MONOTONIC by the two clocks'
    difference now), else this module's import stamp."""
    global _process_start
    if _process_start is None:
        start = _IMPORT_STAMP
        try:
            with open("/proc/self/stat", "rb") as f:
                stat = f.read()
            # field 2, the command, may hold spaces: count from its end
            ticks = int(stat[stat.rindex(b")") + 2:].split()[19])
            kernel = ticks / os.sysconf("SC_CLK_TCK") - (
                time.clock_gettime(time.CLOCK_BOOTTIME) - clock())
            if kernel <= _IMPORT_STAMP:
                start = kernel
        except (OSError, ValueError, IndexError, AttributeError):
            pass
        _process_start = start
    return _process_start


def _compile_state() -> Dict[str, Any]:
    state = getattr(_stack, "compile", None)
    if state is None:
        state = _stack.compile = {}
    return state


@contextlib.contextmanager
def boot_span(name: str):
    """`with tracing.boot_span("boot.engine_build") as facts:` is one row
    of the kept list `boot.spans` (`name`, `t0`, `t1` and whatever the body
    puts into `facts`), whether or not a recording is on: a boot outlives
    any recording, and `boot_summary()` and the benchmark's `setup_*`
    metrics read it long after. While it is open it is the thread's
    innermost span, so a compile request inside it says so (`span`), and in
    a recording it is a span of the ring like any other."""
    facts: Dict[str, Any] = {}
    sp = open_span(name, "boot")
    try:
        yield facts
    finally:
        t1 = sp.close(**facts)
        add_record("boot.spans", dict(facts, name=name, t0=sp.t0, t1=t1))


def compile_event(event: str, **_kw):
    """`jax.monitoring` event listener: the persistent cache's answer to
    the request this thread is making."""
    answer = _JAX_CACHE.get(event)
    if answer is not None:
        _compile_state()["cache"] = answer


def compile_duration(event: str, secs: float, fun_name: str = "", **_kw):
    """`jax.monitoring` duration listener. The clock is stamped when an
    event arrives and its duration subtracted, so a row's `t0` / `t1` lie on
    `clock` beside the spans."""
    if event == _JAX_TRACE:
        # by name: the inner functions' traces lie inside the outermost
        # one's, and a lowering traces inner functions again before it
        # reports, so "the last trace before the lowering" is not it
        traced = getattr(_stack, "traced", None)
        if traced is None or len(traced) >= 256:
            traced = _stack.traced = {}
        traced[fun_name] = (secs, clock())
    elif event == _JAX_LOWER:
        state = _compile_state()
        state.clear()               # a request begins with its lowering
        now = clock()
        state["lower"] = (fun_name, secs, now)
        # the request's own trace: `f` of `jit(f)`, ended before the
        # lowering began and not long before (`TRACE_JOIN_GAP_S`). A jaxpr
        # traced earlier (the engine's `eval_shape` at boot validation) is
        # found again and reported as a trace of no length; where no trace
        # of the name is that near, the row's `trace_s` is None
        trace = getattr(_stack, "traced", {}).pop(fun_name[4:-1], None) \
            if fun_name.startswith("jit(") else None
        if trace is not None \
                and -1e-3 <= now - secs - trace[1] <= TRACE_JOIN_GAP_S:
            state["trace"] = trace
    elif event == _JAX_RETRIEVAL:
        _compile_state()["retrieval_s"] = secs
    elif event == _JAX_SAVED:
        _compile_state()["saved_s"] = secs
    elif event == _JAX_BACKEND:
        _close_compile_request(fun_name, secs)


def _close_compile_request(fun_name: str, backend_s: float):
    t1 = clock()
    state = _compile_state()
    t0 = t1 - backend_s
    # None: no trace of this name ended just before the lowering (the
    # join missed it): unknown, not 0
    trace_s, lower_s = None, 0.0
    lower = state.get("lower")
    if lower is not None and lower[0] == fun_name:
        lower_s = lower[1]
        t0 = min(t0, lower[2] - lower_s)
        trace = state.get("trace")
        if trace is not None:
            trace_s = trace[0]
            t0 = min(t0, trace[1] - trace_s)
    top = current_span()
    row = {"t0": t0, "t1": t1, "fun_name": fun_name, "trace_s": trace_s,
           "lower_s": lower_s, "backend_s": backend_s,
           "cache": state.get("cache", "off"),
           "retrieval_s": state.get("retrieval_s"),
           "saved_s": state.get("saved_s"),
           "tid": threading.get_ident(),
           "span": top.name if top is not None else None}
    state.clear()
    _stack.last_compile = row
    add_record("compile.requests", row)
    if recording:
        # beside the `decode.*` span it interrupted, in the same ring, and
        # caused by it as any span it had opened would be
        facts = {k: v for k, v in row.items()
                 if k not in ("t0", "t1", "tid") and v is not None}
        facts["sid"] = next(_next_sid)
        if top is not None:
            facts["parent"] = top.sid
        record_span("xla.compile", t0, t1 - t0, "compile", facts)


def last_compile_request(since: float = 0.0) -> Optional[Dict[str, Any]]:
    """The row of the compile request this thread closed last, if it
    closed at or after `since` (a caller that timed a `compile()` asks
    for that compile's own row)."""
    row = getattr(_stack, "last_compile", None)
    return row if row is not None and row["t1"] >= since else None


def boot_summary() -> Dict[str, Any]:
    """What this process spent before it served, computed when asked from
    the kept rows (`DecodeEngine.status()["boot"]`): seconds from the
    process's start to this module's import and to the first compile
    request, seconds by boot span name, and of the compile requests their
    count, the cache's answers, the sums of tracing, lowering and the
    backend's time, how many have no trace of their own (`untraced`: the
    join missed it, so `trace_s` is a lower bound by that many programs),
    the seconds of the clock they cover
    together, and the five slowest."""
    start = process_start()
    rows = get_records("compile.requests")
    by_span: Dict[str, float] = {}
    for sp in get_records("boot.spans"):
        by_span[sp["name"]] = by_span.get(sp["name"], 0.0) \
            + sp["t1"] - sp["t0"]
    covered, end = 0.0, float("-inf")
    for r in sorted(rows, key=lambda r: r["t0"]):
        covered += max(0.0, r["t1"] - max(r["t0"], end))
        end = max(end, r["t1"])
    slowest = sorted(rows, key=lambda r: r["t0"] - r["t1"])[:5]
    return {
        "process_start": start,
        # the interpreter and the imports up to this module (JAX's among
        # them); what `first_program_s` holds beyond it is the backend's
        # start-up and the caller's own work before its first program
        "imported_s": _IMPORT_STAMP - start,
        "first_program_s": min(r["t0"] for r in rows) - start
        if rows else None,
        "spans_s": by_span,
        "compile": {
            "requests": len(rows),
            "hits": sum(r["cache"] == "hit" for r in rows),
            "misses": sum(r["cache"] == "miss" for r in rows),
            "uncached": sum(r["cache"] == "off" for r in rows),
            "untraced": sum(r["trace_s"] is None for r in rows),
            "trace_s": sum(r["trace_s"] or 0.0 for r in rows),
            "lower_s": sum(r["lower_s"] for r in rows),
            "backend_s": sum(r["backend_s"] for r in rows),
            "covered_s": covered,
            "slowest": [{"fun_name": r["fun_name"],
                         "seconds": r["t1"] - r["t0"],
                         "cache": r["cache"], "span": r["span"]}
                        for r in slowest]}}


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

# Host/step spans get stable synthetic pids so the tracks group cleanly in
# the viewer; device traces keep their own pids (offset on collision is
# unnecessary — jax pids are real OS pids, far from these).
_PID_BY_CAT = {"host": 1, "step": 2}


def spans_to_chrome_events(spans: Sequence[Span]) -> List[dict]:
    events = []
    tids: Dict[int, int] = {}
    for s in spans:
        tid = tids.setdefault(s.tid, len(tids))
        ev = {"name": s.name, "ph": "X",
              "pid": _PID_BY_CAT.get(s.cat, 3), "tid": tid,
              "ts": s.ts * 1e6, "dur": s.dur * 1e6, "cat": s.cat}
        if s.args:
            ev["args"] = {k: v for k, v in s.args.items()}
        events.append(ev)
    return events


def _load_chrome_trace(path: str) -> List[dict]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", []) if isinstance(data, dict) else data
    for e in events:
        e.setdefault("cat", "device")
    return events


def find_device_traces(trace_dir: str) -> List[str]:
    """The jax profiler writes plugins/profile/<run>/<host>.trace.json.gz;
    accept plain .trace.json too."""
    hits = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        hits.extend(glob.glob(os.path.join(trace_dir, pat),
                              recursive=True))
    return sorted(set(hits))


def merge_chrome_traces(event_lists: Sequence[Sequence[dict]]) -> dict:
    merged: List[dict] = []
    for evs in event_lists:
        merged.extend(evs)
    return {"traceEvents": merged, "displayTimeUnit": "ms"}


_warned_dropped = [False]


def export_trace(path: str, trace_dir: Optional[str] = None,
                 spans: Optional[Sequence[Span]] = None) -> str:
    """Write ONE chrome trace: the unified span store (host + step +
    whatever else was recorded) plus every jax device trace found under
    `trace_dir`. Returns `path`. Warns ONCE per process when the ring
    evicted spans — the export window is then missing its oldest spans
    and the reader should know rather than trust a silently truncated
    timeline (the same drop count feeds the
    paddle_tpu_spans_dropped_total counter)."""
    if dropped_spans() and not _warned_dropped[0]:
        _warned_dropped[0] = True
        import logging

        logging.getLogger("paddle_tpu.observability").warning(
            "export_trace: the span ring dropped %d span(s) (oldest "
            "evicted past MAX_SPANS=%d) — the exported window is "
            "incomplete at its start", dropped_spans(), MAX_SPANS)
    lists = [spans_to_chrome_events(
        spans if spans is not None else get_spans())]
    if trace_dir and os.path.isdir(trace_dir):
        for p in find_device_traces(trace_dir):
            try:
                lists.append(_load_chrome_trace(p))
            except (OSError, ValueError):
                continue  # truncated trace from a killed run: skip, keep ours
    trace = merge_chrome_traces(lists)
    from ..resilience.atomic import json_dump as _atomic_json_dump

    _atomic_json_dump(trace, path)
    return path


def save_spans(path: str) -> str:
    """Persist raw spans as JSON (spans.json in a run dir) so
    tools/obsdump.py can rebuild a trace offline."""
    from ..resilience.atomic import json_dump as _atomic_json_dump

    _atomic_json_dump([s._asdict() for s in get_spans()], path)
    return path


# ---------------------------------------------------------------------------
# Cross-process trace reassembly (the obsdump `trace --trace-id` backend)
# ---------------------------------------------------------------------------


def read_trace_dir(trace_dir: str) -> List[Dict[str, Any]]:
    """Every sampled-span record from every process sink under
    `trace_dir` (router + N replicas + PS servers each wrote their own
    trace-<pid>-<suffix>.jsonl). Malformed lines are skipped — a killed
    process can leave at most a torn tail, and the atomic-rewrite sink
    makes even that unlikely."""
    out: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(trace_dir,
                                              "trace-*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and rec.get("trace_id") \
                            and rec.get("span_id"):
                        out.append(rec)
        except OSError:
            continue
    return out


def trace_summaries(records: Sequence[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """One row per trace_id (newest first): span count, distinct
    processes, the root span's name, start time, total duration."""
    by_tid: Dict[str, List[Dict[str, Any]]] = {}
    for r in records:
        by_tid.setdefault(r["trace_id"], []).append(r)
    rows = []
    for tid, recs in by_tid.items():
        ids = {r["span_id"] for r in recs}
        roots = [r for r in recs
                 if not r.get("parent_span_id")
                 or r["parent_span_id"] not in ids]
        roots.sort(key=lambda r: r.get("ts", 0.0))
        t0 = min(r.get("ts", 0.0) for r in recs)
        t1 = max(r.get("ts", 0.0) + r.get("dur", 0.0) for r in recs)
        rows.append({
            "trace_id": tid, "spans": len(recs),
            "processes": len({r.get("pid") for r in recs}),
            "root": roots[0]["name"] if roots else "?",
            "start_ts": t0, "wall_ms": round((t1 - t0) * 1000, 3)})
    rows.sort(key=lambda r: r["start_ts"], reverse=True)
    return rows


def build_trace_tree(records: Sequence[Dict[str, Any]], trace_id: str
                     ) -> List[Dict[str, Any]]:
    """Reassemble one trace's span TREE across processes: nodes are the
    sink records plus a `children` list, linked on parent_span_id and
    ordered by wall-clock start. Spans whose parent was never recorded
    (an unflushed/killed process, or the parent lives in an untraced
    tier) surface as additional roots rather than vanishing."""
    nodes = {r["span_id"]: dict(r, children=[])
             for r in records if r.get("trace_id") == trace_id}
    roots: List[Dict[str, Any]] = []
    for node in nodes.values():
        parent = node.get("parent_span_id")
        if parent and parent in nodes and parent != node["span_id"]:
            nodes[parent]["children"].append(node)
        else:
            roots.append(node)

    def _sort(children):
        children.sort(key=lambda n: n.get("ts", 0.0))
        for c in children:
            _sort(c["children"])

    _sort(roots)
    return roots


def trace_records_to_chrome(records: Sequence[Dict[str, Any]]
                            ) -> List[dict]:
    """Sink records → chrome trace events. Unlike the in-process ring
    (perf_counter epoch per process), sink records carry wall-clock
    start times, so spans from different processes line up on one
    timeline; pids are the real OS pids."""
    events = []
    tids: Dict[tuple, int] = {}
    for r in records:
        pid = int(r.get("pid", 0))
        tid = tids.setdefault((pid, r.get("tid", 0)), len(tids))
        ev = {"name": r.get("name", "?"), "ph": "X", "pid": pid,
              "tid": tid, "ts": float(r.get("ts", 0.0)) * 1e6,
              "dur": float(r.get("dur", 0.0)) * 1e6,
              "cat": r.get("cat", "trace")}
        args = dict(r.get("args") or {})
        args["trace_id"] = r.get("trace_id")
        args["span_id"] = r.get("span_id")
        if r.get("parent_span_id"):
            args["parent_span_id"] = r["parent_span_id"]
        ev["args"] = args
        events.append(ev)
    return events
