"""Host-overlap execution primitives: lazy fetches, bounded in-flight
windows, and background prefetch stages.

The synchronous feed→run→fetch rhythm the reference executor interprets
by (executor.cc:178) leaves the device idle for the whole host round
trip every step — BENCH r05 measured 39.4 ms steps at MFU 0.0156 on a
2.7 ms computation. jax already dispatches asynchronously; what the
framework must add is the discipline to *exploit* that without
unbounded device memory:

  FetchHandle     a lazy fetch future: `Executor.run(..., sync=False)`
                  returns device arrays wrapped in one of these, and
                  nothing touches the host until `.result()`. Resolving
                  records dispatch-to-ready latency and (when the host
                  actually waited) host-blocked seconds, then DROPS the
                  device references so the buffers free.
  InFlightWindow  bounds how many unresolved handles may exist at once
                  (default 2): admitting past the limit resolves the
                  oldest first, so a runaway producer can never pile up
                  device-resident fetch buffers.
  Prefetcher      a bounded background stage over any iterator — the
                  host-side collate queue (transfer=None) or the
                  device-transfer stage (transfer=jax.device_put,
                  sharded over the active SPMD mesh). Producer errors
                  propagate to the consumer; close() drains and joins
                  the thread (tf.data-style prefetch-to-device,
                  Murray et al.).

Telemetry rides through observability.telemetry: host_blocked seconds
per site, dispatch-to-ready histogram, prefetch queue-depth gauge, and
pipeline_stall events for blocks past PADDLE_TPU_STALL_EVENT_S.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable, List, Optional

import numpy as np

from ..observability import telemetry as _telemetry

__all__ = ["FetchHandle", "InFlightWindow", "Prefetcher",
           "DevicePrefetcher", "mesh_device_put", "inflight_stats",
           "reset_inflight_stats", "DEFAULT_IN_FLIGHT",
           "device_prefetch_wanted", "stream_window_default"]

# Two windows in flight: one computing on device, one whose fetches the
# host may still be consuming — the classic double buffer. More only
# helps when step times are wildly uneven, and every extra slot is a
# full window of fetch buffers held in device memory.
DEFAULT_IN_FLIGHT = 2


# -- in-flight accounting (feeds the tests' live-buffer assertions) ---------

_acct_lock = threading.Lock()
_open_handles = 0
_open_high_water = 0


def _track_open():
    global _open_handles, _open_high_water
    with _acct_lock:
        _open_handles += 1
        if _open_handles > _open_high_water:
            _open_high_water = _open_handles
        n = _open_handles
    _telemetry.record_async_inflight(n)


def _track_close():
    global _open_handles
    with _acct_lock:
        _open_handles = max(0, _open_handles - 1)
        n = _open_handles
    _telemetry.record_async_inflight(n)


def inflight_stats() -> dict:
    """{open, high_water} unresolved FetchHandles — the accounting the
    in-flight-cap tests assert against alongside jax.live_arrays()."""
    with _acct_lock:
        return {"open": _open_handles, "high_water": _open_high_water}


def reset_inflight_stats():
    global _open_high_water
    with _acct_lock:
        _open_high_water = _open_handles


def _all_ready(values) -> bool:
    """Best-effort readiness probe: jax arrays expose is_ready() (0.4+);
    anything without it (numpy, python scalars) is ready by definition."""
    for v in values:
        probe = getattr(v, "is_ready", None)
        if probe is None:
            continue
        try:
            if not probe():
                return False
        except Exception:
            return False
    return True


class FetchHandle:
    """A lazy fetch: holds the executor's device-resident fetch values
    and converts them to numpy only on `result()`. The device references
    are dropped at resolution, so a resolved handle holds no
    accelerator memory; the numpy result is cached and re-readable.

    `transform`, when given, maps the resolved numpy list to the final
    value `result()` returns (the serving predictor uses it for its
    pad-slice postprocessing)."""

    __slots__ = ("_values", "_result", "_resolved", "_site", "_transform",
                 "_lock", "n_steps", "start_step")

    def __init__(self, values: Iterable[Any], site: str = "executor",
                 transform: Optional[Callable[[List[np.ndarray]], Any]]
                 = None):
        self._values: Optional[List[Any]] = list(values)
        self._result: Any = None
        self._resolved = False
        self._site = site
        self._transform = transform
        from ..analysis import lockcheck as _lockcheck  # deferred

        self._lock = _lockcheck.Lock("core.async_exec.FetchHandle._lock")
        # run_stream stamps these so drivers can map a window handle
        # back to global step numbers without side tables
        self.n_steps: Optional[int] = None
        self.start_step: Optional[int] = None
        _track_open()

    def ready(self) -> bool:
        """True when resolving would not block (already resolved, or
        every device value reports ready). Lock-free on purpose: a
        monitor thread probing readiness must not serialize behind a
        resolver blocked in the device wait."""
        if self._resolved:
            return True
        values = self._values
        if values is None:  # raced a resolve that just completed
            return True
        return _all_ready(values)

    def result(self, stall: bool = True) -> Any:
        """Block until the fetches are ready, convert to numpy, release
        the device references, and return (cached afterwards).
        stall=False classifies the block as the caller's normal rhythm
        (window backpressure keeping the host coupled to the device) —
        it still counts as host-blocked time but not as a pipeline
        stall event."""
        with self._lock:
            if self._resolved:
                return self._result
            values = self._values
            was_ready = _all_ready(values)
            t0 = time.perf_counter()
            try:
                import jax

                jax.block_until_ready(
                    [v for v in values if hasattr(v, "block_until_ready")
                     or hasattr(v, "devices")])
            except Exception:  # lint-exempt:swallow: non-jax values (numpy, scalars) need no wait
                pass  # non-jax values (numpy, scalars) need no wait
            out = [np.asarray(v) for v in values]
            now = time.perf_counter()
            if not was_ready:
                _telemetry.record_host_blocked(
                    "fetch:" + self._site, now - t0, stall=stall)
            if self._transform is not None:
                out = self._transform(out)
            self._result = out
            self._values = None  # device buffers free here
            self._resolved = True
        _track_close()
        return self._result

    def map(self, fn: Callable[[Any], Any]) -> "FetchHandle":
        """Compose `fn` onto the resolution result: unresolved handles
        apply it lazily after the existing transform; resolved handles
        apply it to the cached result now. Returns self (chainable) —
        the public way to stack postprocessing without touching the
        handle's internals."""
        with self._lock:
            if self._resolved:
                self._result = fn(self._result)
            else:
                inner = self._transform
                self._transform = (
                    (lambda arrs: fn(inner(arrs))) if inner is not None
                    else fn)
        return self

    # numpy interop for single- and multi-value handles
    def __array__(self, dtype=None):
        out = self.result()
        arr = np.asarray(out[0] if isinstance(out, list) and len(out) == 1
                         else out)
        return arr.astype(dtype) if dtype is not None else arr

    def __len__(self):
        out = self.result()
        return len(out)

    def __getitem__(self, i):
        return self.result()[i]

    def __iter__(self):
        return iter(self.result())

    def raw(self) -> Optional[List[Any]]:
        """The unresolved device values (None once resolved) — for
        callers that want to keep computing on device."""
        with self._lock:
            return None if self._resolved else list(self._values)

    def __del__(self):
        # a dropped, never-resolved handle must not leak the in-flight
        # accounting (the buffers themselves free with the refs)
        try:
            if not self._resolved:
                _track_close()
        except Exception:  # lint-exempt:swallow: best-effort gauge accounting in a destructor path
            pass


class InFlightWindow:
    """Bound on unresolved FetchHandles: admitting past `limit` resolves
    the oldest handle first (blocking until its step finished), so at
    most `limit` windows of fetch buffers are ever device-resident.
    This is the backpressure that couples the host's run-ahead to the
    device's actual progress."""

    def __init__(self, limit: int = DEFAULT_IN_FLIGHT,
                 site: str = "stream"):
        self.limit = max(1, int(limit))
        self.site = site
        self._dq: "deque[FetchHandle]" = deque()
        self.high_water = 0

    def reserve(self):
        """Make room for one more handle: resolve oldest until at most
        limit-1 remain. Call BEFORE dispatching the next window so the
        new handle's buffers never coexist with a full window.
        Backpressure resolution is the window doing its job, not a
        pipeline stall — resolved with stall=False."""
        while len(self._dq) >= self.limit:
            self._dq.popleft().result(stall=False)

    def admit(self, handle: FetchHandle) -> FetchHandle:
        self.reserve()
        self._dq.append(handle)
        if len(self._dq) > self.high_water:
            self.high_water = len(self._dq)
        return handle

    def drain(self):
        """Resolve everything outstanding (end of stream / shutdown)."""
        while self._dq:
            self._dq.popleft().result(stall=False)


# ---------------------------------------------------------------------------
# Prefetch stages
# ---------------------------------------------------------------------------


def mesh_device_put(batch, mesh=None, axis: Optional[str] = None):
    """Transfer a feed batch (dict/pytree of arrays) to device ahead of
    the step that consumes it. Under an active SPMD mesh (mesh_guard),
    array leaves whose leading dim divides the mesh's data axis go up
    already sharded over it — the transfer the step would otherwise
    perform synchronously at dispatch; everything else is replicated."""
    import jax

    if mesh is None:
        try:
            from ..parallel.mesh import current_mesh

            mesh = current_mesh()
        except Exception:
            mesh = None
    if mesh is None:
        return jax.tree_util.tree_map(jax.device_put, batch)
    from jax.sharding import NamedSharding, PartitionSpec as P

    names = list(mesh.axis_names)
    ax = axis if axis in names else ("dp" if "dp" in names else names[0])
    n = int(mesh.shape[ax])

    def put(x):
        shape = getattr(x, "shape", None)
        if shape and len(shape) >= 1 and shape[0] % n == 0:
            return jax.device_put(x, NamedSharding(mesh, P(ax)))
        return jax.device_put(x, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(put, batch)


class Prefetcher:
    """Bounded background stage over an iterator: a daemon thread pulls
    from `src`, applies `transfer` (e.g. mesh_device_put), and parks
    results in a queue of `depth` slots; iteration consumes them.

    Lifecycle contract (the reader.py producer-thread fix lives here):
      - an exception in `src` or `transfer` is re-raised to the
        consumer at the point of iteration, not swallowed;
      - `close()` (also called by the iterator's GC/`with` exit and on
        exhaustion) signals the thread, drains the queue so a blocked
        put unblocks, and joins — no leaked thread when the consumer
        exits early.
    """

    _DONE = "done"
    _ITEM = "item"
    _ERROR = "error"

    def __init__(self, src: Iterable, depth: int = 2,
                 transfer: Optional[Callable[[Any], Any]] = None,
                 stage: str = "host"):
        self._src = src
        self._transfer = transfer
        self._stage = stage
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._exhausted = False
        self._thread = threading.Thread(
            target=self._produce, daemon=True,
            name=f"paddle-tpu-prefetch-{stage}")
        self._thread.start()

    # -- producer side -------------------------------------------------

    def _put(self, msg) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            for item in self._src:
                if self._stop.is_set():
                    return
                if self._transfer is not None:
                    item = self._transfer(item)
                if not self._put((self._ITEM, item)):
                    return
                _telemetry.record_prefetch_item(self._stage)
                _telemetry.record_prefetch_depth(self._stage,
                                                 self._q.qsize())
        except BaseException as e:  # propagate, never swallow
            self._put((self._ERROR, e))
        else:
            self._put((self._DONE, None))

    # -- consumer side -------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        empty = self._q.empty()
        t0 = time.perf_counter()
        kind, val = self._q.get()
        if empty:
            # the consumer outran the producer: input-bound time
            _telemetry.record_host_blocked(
                "prefetch:" + self._stage, time.perf_counter() - t0)
        _telemetry.record_prefetch_depth(self._stage, self._q.qsize())
        if kind == self._ITEM:
            return val
        self._exhausted = True
        self.close()
        if kind == self._ERROR:
            raise val
        raise StopIteration

    def close(self):
        """Idempotent shutdown: stop the producer, unblock it, join."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            self._thread.join(timeout=5)

    @property
    def thread(self) -> threading.Thread:
        return self._thread

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self._stop.set()
        except Exception:  # lint-exempt:swallow: interpreter-teardown __del__: Event may be gone
            pass


def DevicePrefetcher(src: Iterable, depth: int = 2, mesh=None,
                     axis: Optional[str] = None) -> Prefetcher:
    """Prefetcher whose transfer stage is jax.device_put (sharded over
    the active SPMD mesh when one is in scope) — while step N computes,
    batch N+1 is already on device and batch N+2 is being produced by
    whatever host stage feeds this one."""
    return Prefetcher(src, depth=depth,
                      transfer=lambda b: mesh_device_put(b, mesh=mesh,
                                                         axis=axis),
                      stage="device")


def device_prefetch_wanted(places, double_buffer: bool) -> bool:
    """One gate for every loader: prefetch-to-DEVICE only where a
    transfer exists to hide. PADDLE_TPU_DEVICE_PREFETCH=1|0 overrides
    unconditionally (even against double_buffer=False); otherwise the
    double-buffer flag must be on AND `places` must include an
    accelerator — CPU places keep yielding mutable numpy, since the
    put stage there is pure overhead (PROFILE.md §Pipeline)."""
    raw = os.environ.get("PADDLE_TPU_DEVICE_PREFETCH")
    if raw is not None and raw.strip() in ("0", "1"):
        return raw.strip() == "1"
    if not double_buffer or places is None:
        return False
    from .places import CPUPlace

    if not isinstance(places, (list, tuple)):
        places = [places]  # the fluid API accepts a bare place
    return any(not isinstance(p, CPUPlace) for p in places)


def stream_window_default() -> int:
    """Window size for the streaming drivers (PADDLE_TPU_STREAM_WINDOW,
    default 8): steps micro-chained per dispatch. 1 disables streaming."""
    raw = os.environ.get("PADDLE_TPU_STREAM_WINDOW")
    if not raw:
        return 8
    try:
        return max(1, int(raw))
    except ValueError:
        return 8
