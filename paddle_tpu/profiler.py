"""Profiler (reference: python/paddle/fluid/profiler.py:228 context manager
→ C++ host profiler + CUPTI DeviceTracer, SURVEY §5 'Tracing/profiling').

TPU-native: jax.profiler captures both host and device timelines into
XPlane/perfetto traces — the role of profiler.proto + tools/timeline.py.
`RecordEvent`-style op annotation maps to jax.profiler.TraceAnnotation;
the host-side span record lands in the unified observability span store
(observability/tracing.py), so `export_chrome_tracing` emits ONE trace
holding RecordEvent host spans, executor/trainer step-telemetry spans,
and the jax device timeline."""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
import time
from collections import defaultdict
from typing import Dict, Optional

import jax

from .observability import tracing as _tracing

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "RecordEvent", "cuda_profiler", "npu_profiler",
           "export_chrome_tracing", "capture_profile", "ProfilerBusyError",
           "PROFILE_DIR_ENV", "MAX_CAPTURE_SECONDS"]

_trace_dir: Optional[str] = None
_host_events = defaultdict(list)
_active = False


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default"):
    """reference: profiler.py:228 — `with profiler.profiler('All'):`"""
    start_profiler(state, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def start_profiler(state="All", profile_path="/tmp/profile", tracer_option=None):
    global _trace_dir, _active
    if _active:
        raise RuntimeError(
            "start_profiler called while a trace is already active; call "
            "stop_profiler() first (nested/overlapping jax traces are not "
            "supported)")
    _trace_dir = profile_path if os.path.isdir(profile_path) or not \
        os.path.splitext(profile_path)[1] else os.path.dirname(profile_path)
    os.makedirs(_trace_dir or ".", exist_ok=True)
    jax.profiler.start_trace(_trace_dir)
    _active = True


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Safe no-op when no trace was started — a teardown path may call it
    unconditionally."""
    global _active
    if _active:
        jax.profiler.stop_trace()
        _active = False
    _print_host_events(sorted_key)


def reset_profiler():
    """Clear ALL host-side profiler state: the aggregate event table, the
    unified span store, and the remembered trace dir (so one test's trace
    path cannot leak into the next export)."""
    global _trace_dir
    _host_events.clear()
    _tracing.clear_spans()
    _trace_dir = None


def trace_dir() -> Optional[str]:
    """Directory the current/last jax trace wrote into (None after
    reset)."""
    return _trace_dir


def _print_host_events(sorted_key=None):
    if not _host_events:
        return
    rows = []
    for name, times in _host_events.items():
        total = sum(times)
        rows.append((name, len(times), total, total / len(times)))
    if sorted_key in (None, "total"):
        rows.sort(key=lambda r: -r[2])
    elif sorted_key == "calls":
        rows.sort(key=lambda r: -r[1])
    print(f"{'Event':40s} {'Calls':>8s} {'Total(ms)':>12s} {'Avg(ms)':>10s}")
    for name, calls, total, avg in rows:
        print(f"{name:40s} {calls:8d} {total * 1e3:12.3f} {avg * 1e3:10.3f}")


class RecordEvent:
    """reference: platform/profiler.h:81 RecordEvent RAII — host-side named
    span + device TraceAnnotation. The host span is recorded with
    cat="host" in the unified store."""

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *a):
        self._ann.__exit__(*a)
        dur = time.perf_counter() - self._t0
        _host_events[self.name].append(dur)
        _tracing.record_span(self.name, self._t0, dur, cat="host")
        return False


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """reference: profiler.py:39 — accelerator-profiler passthrough."""
    with profiler(profile_path=output_file or "/tmp/profile"):
        yield


npu_profiler = cuda_profiler


# ---------------------------------------------------------------------------
# On-demand bounded capture (the POST /v1/profile backend)
# ---------------------------------------------------------------------------

PROFILE_DIR_ENV = "PADDLE_TPU_PROFILE_DIR"
MAX_CAPTURE_SECONDS = 120.0
MIN_CAPTURE_SECONDS = 0.05

_capture_lock = threading.Lock()


class ProfilerBusyError(RuntimeError):
    """A capture (or a manually started trace) is already running.
    The jax profiler supports exactly one active trace per process, so
    concurrent /v1/profile requests must 409, not queue — a queued
    capture would measure a different window than the caller asked
    about."""


def capture_profile(seconds: float,
                    out_dir: Optional[str] = None) -> Dict[str, object]:
    """One bounded profiling window: jax host+device trace for
    `seconds`, then a merged chrome trace plus the live perf/memory
    attribution snapshot, written into a fresh artifact directory.

    Returns {"dir", "trace", "perf", "seconds"} — `trace` is the merged
    chrome://tracing JSON (unified span store + jax device timeline),
    `perf` a JSON sidecar holding the perfwatch MFU/step-time snapshot
    and the memwatch owner table taken at window close.

    Raises ProfilerBusyError when a capture or a user-started
    start_profiler() trace is active. Blocks the calling thread for the
    window — HTTP servers routing here are threaded, so the process
    keeps serving while the trace runs.
    """
    global _active
    seconds = min(max(float(seconds), MIN_CAPTURE_SECONDS),
                  MAX_CAPTURE_SECONDS)
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusyError("a profile capture is already running")
    try:
        base = os.environ.get(PROFILE_DIR_ENV)
        if out_dir is None:
            if base:
                os.makedirs(base, exist_ok=True)
            out_dir = tempfile.mkdtemp(prefix="paddle-tpu-profile-",
                                       dir=base or None)
        try:
            start_profiler(profile_path=out_dir)
        except RuntimeError as e:
            raise ProfilerBusyError(str(e)) from e
        t0 = time.time()
        try:
            # the program's own spans (the decode loop's, the front's)
            # record for the window and lie in the trace as annotations
            with _tracing.recorded(clear=False):
                time.sleep(seconds)
        finally:
            # stop directly rather than via stop_profiler(): the
            # aggregate host-event table printing belongs to the
            # interactive API, not an HTTP handler's stdout
            jax.profiler.stop_trace()
            _active = False
        trace_path = _tracing.export_trace(
            os.path.join(out_dir, "trace.json"), trace_dir=out_dir)
        perf_path = os.path.join(out_dir, "perf.json")
        from .observability import events as _events
        from .observability import memwatch as _memwatch
        from .observability import perfwatch as _perfwatch
        from .observability import telemetry as _telemetry

        perf = {
            "window_seconds": seconds,
            "started_at": t0,
            "perfwatch": _perfwatch.snapshot(),
            "memory": _memwatch.status_block(),
            "host_blocked_seconds_total":
                _telemetry.host_blocked_total(),
        }
        from .resilience.atomic import json_dump as _json_dump
        _json_dump(perf, perf_path, indent=2, sort_keys=True,
                   default=str)
        _events.emit("profile", dir=out_dir, seconds=seconds,
                     trace=trace_path)
        return {"dir": out_dir, "trace": trace_path, "perf": perf_path,
                "seconds": seconds}
    finally:
        _capture_lock.release()


def export_chrome_tracing(path, events=None):
    """Write ONE chrome://tracing JSON file (reference: tools/timeline.py:131
    converted profiler.proto to chrome trace): the unified span store
    (RecordEvent host spans, cat="host"; step telemetry, cat="step") plus
    the jax.profiler device timeline when a trace dir is known.

    `events`, if given, is the legacy list of (name, start_s, dur_s)
    tuples and is exported verbatim instead of the span store."""
    spans = None
    if events is not None:
        spans = [_tracing.Span(name, start, dur, "host", 0, None)
                 for name, start, dur in events]
    return _tracing.export_trace(path, trace_dir=_trace_dir, spans=spans)
