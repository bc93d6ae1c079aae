"""Runtime concurrency sanitizer: instrumented lock factories.

The static prong (`tools/lockgraph.py`) proves lock-ORDER safety by
walking the AST; this module is the runtime prong that catches what
statics cannot see — the deadlock that actually forms, the inversion a
dynamic call path takes, the lock a hot thread sits on for seconds.
The repo's worst recent bugs were exactly this class (the PR 10
rendezvous cross-generation deadlock, the half-open breaker probe-slot
wedge), all found by chaos runs or review instead of tooling.

`PADDLE_TPU_LOCKCHECK` gates everything:

  0 (default)  the factories return RAW `threading` primitives —
               zero overhead, zero behavior change.
  1            instrumented: per-thread acquisition stacks, held-
               seconds / contention metrics, observed lock-order edges
               checked against the committed `tools/lock_order.json`
               ledger (an edge the ledger orders the OTHER way counts
               as an inversion).
  2            level 1 plus live deadlock detection: a blocking
               `acquire()` registers in a waits-for graph and polls; a
               cycle raises `DeadlockError` naming every thread and
               held lock in it INSTEAD of hanging forever.

Our own modules create their contended locks through these factories
(the monkeypatch hook — `self._cv = lockcheck.Condition(name=...)`),
passing the same canonical site id `tools/lockgraph.py` infers
statically (`<module>.<Class>.<attr>`, e.g.
`serving.batcher.Batcher._cv`), so the static ledger and the runtime
observations speak one naming scheme.

Metrics (through the PR 1 registry, lazily — this module stays
importable before the package finishes initializing):

  paddle_tpu_lock_held_seconds{site}            histogram
  paddle_tpu_lock_contention_total{site}        counter
  paddle_tpu_lock_inversions_total{first,second} counter
  paddle_tpu_lock_deadlocks_total               counter

Known limits (documented, not hidden): the checker's own bookkeeping
uses one raw mutex; `Condition.wait()` re-acquisition blocks inside the
stdlib so a deadlock formed THERE is not detected; RLock re-entry
observes a held-span per acquire/release pair.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "ENV_VAR", "level", "Lock", "RLock", "Condition", "DeadlockError",
    "set_ledger", "ledger_order", "observed_edges",
    "observed_inversions", "deadlock_count", "reset",
]

ENV_VAR = "PADDLE_TPU_LOCKCHECK"
LEDGER_ENV_VAR = "PADDLE_TPU_LOCK_ORDER"

# how often a level-2 blocked acquire re-runs cycle detection; also the
# bound on how long a freshly-formed deadlock goes unnoticed
_POLL_S = 0.05

_DEFAULT_LEDGER = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools", "lock_order.json")


def level() -> int:
    """PADDLE_TPU_LOCKCHECK parsed: 0 off (default), 1 observe,
    2 observe + deadlock detection. Junk values mean off — the
    sanitizer must never be the thing that breaks a run by accident."""
    raw = os.environ.get(ENV_VAR)
    if not raw:
        return 0
    try:
        return max(0, min(2, int(raw)))
    except ValueError:
        return 0


class DeadlockError(RuntimeError):
    """Raised (level 2) from a blocking `acquire()` whose waits-for
    graph closed into a cycle. `.cycle` holds one dict per thread in
    the cycle: {thread, waits_for, held}."""

    def __init__(self, cycle: List[dict]):
        self.cycle = list(cycle)
        lines = [f"deadlock detected: {len(self.cycle)} thread(s) in "
                 f"a lock cycle:"]
        for hop in self.cycle:
            held = ", ".join(hop["held"]) or "<nothing>"
            lines.append(
                f"  thread '{hop['thread']}' waits for lock "
                f"'{hop['waits_for']}' while holding: {held}")
        super().__init__("\n".join(lines))


# ---------------------------------------------------------------------------
# metrics (lazy: the registry may not be importable yet when an early
# module creates its first lock)
# ---------------------------------------------------------------------------

_metrics: Optional[dict] = None


def _get_metrics() -> Optional[dict]:
    global _metrics
    if _metrics is None:
        try:
            from ..observability import metrics as _m
        except ImportError:
            return None  # package still booting; retry on next event
        _metrics = {
            "held": _m.histogram(
                "paddle_tpu_lock_held_seconds",
                "Seconds a lock was held, per acquisition",
                labelnames=("site",),
                buckets=_m.exponential_buckets(0.0001, 4, 10)),
            "contention": _m.counter(
                "paddle_tpu_lock_contention_total",
                "Blocking acquires that found the lock already held",
                labelnames=("site",)),
            "inversions": _m.counter(
                "paddle_tpu_lock_inversions_total",
                "Acquisitions whose held->acquired edge contradicts the "
                "lock_order.json ledger",
                labelnames=("first", "second")),
            "deadlocks": _m.counter(
                "paddle_tpu_lock_deadlocks_total",
                "Deadlock cycles detected (and broken) by DeadlockError"),
        }
    return _metrics


# ---------------------------------------------------------------------------
# the ledger (blessed global lock order, shared with tools/lockgraph.py)
# ---------------------------------------------------------------------------

_ledger_index: Optional[Dict[str, int]] = None
_ledger_exempt: Optional[set] = None
_ledger_override: Optional[List[str]] = None
_ledger_exempt_override: Optional[set] = None


def _load_ledger() -> Dict[str, int]:
    global _ledger_index, _ledger_exempt
    if _ledger_index is not None:
        return _ledger_index
    if _ledger_override is not None:
        _ledger_index = {s: i for i, s in enumerate(_ledger_override)}
        _ledger_exempt = set(_ledger_exempt_override or ())
        return _ledger_index
    path = os.environ.get(LEDGER_ENV_VAR) or _DEFAULT_LEDGER
    order: List[str] = []
    exempt: set = set()
    try:
        with open(path) as f:
            data = json.load(f)
        order = list(data.get("order", []))
        # exempt_edges suppress justified edges from BOTH prongs — a
        # blessed edge must not fail the runtime gate either
        exempt = {(e.get("first"), e.get("second"))
                  for e in data.get("exempt_edges", [])}
    except (OSError, ValueError):
        pass  # no ledger -> no inversion checks, everything else works
    _ledger_index = {s: i for i, s in enumerate(order)}
    _ledger_exempt = exempt
    return _ledger_index


def _exempt_pairs() -> set:
    _load_ledger()
    return _ledger_exempt or set()


def set_ledger(order: Optional[List[str]],
               exempt_edges: Optional[List[dict]] = None):
    """Test hook: replace (list) or restore (None) the blessed order
    (and, optionally, the exempt edge pairs)."""
    global _ledger_override, _ledger_index, _ledger_exempt
    global _ledger_exempt_override
    _ledger_override = list(order) if order is not None else None
    _ledger_exempt_override = (
        {(e.get("first"), e.get("second")) for e in exempt_edges}
        if exempt_edges is not None else None)
    _ledger_index = None
    _ledger_exempt = None


def ledger_order() -> List[str]:
    idx = _load_ledger()
    return sorted(idx, key=idx.get)


# ---------------------------------------------------------------------------
# the checker: one process-global waits-for/held bookkeeper
# ---------------------------------------------------------------------------


class _Checker:
    """All maps guarded by one raw mutex (`_mu`) held only for dict
    surgery — never across a blocking call, never across a metric
    observation (the registry has its own lock)."""

    def __init__(self):
        self._mu = threading.Lock()
        # id(ilock) -> {thread_ident: recursion count}
        self.holders: Dict[int, Dict[int, int]] = {}
        # thread_ident -> ilock it is blocked acquiring
        self.waiting: Dict[int, "_InstrumentedLock"] = {}
        # thread_ident -> [(ilock, t_acquired)] acquisition stack
        self.held: Dict[int, List[Tuple["_InstrumentedLock", float]]] = {}
        # observed order edges: (first_site, second_site) -> count
        self.edges: Dict[Tuple[str, str], int] = {}
        # inverted edges: (first_site, second_site) -> count
        self.inversions: Dict[Tuple[str, str], int] = {}
        self.deadlocks = 0

    # -- acquisition bookkeeping --------------------------------------

    def on_acquired(self, ilock: "_InstrumentedLock"):
        tid = threading.get_ident()
        new_inversions: List[Tuple[str, str]] = []
        with self._mu:
            self.holders.setdefault(id(ilock), {})
            self.holders[id(ilock)][tid] = \
                self.holders[id(ilock)].get(tid, 0) + 1
            stack = self.held.setdefault(tid, [])
            for prev, _t0 in stack:
                if prev is ilock or prev.name == ilock.name:
                    continue  # re-entry / per-instance same-site locks
                edge = (prev.name, ilock.name)
                self.edges[edge] = self.edges.get(edge, 0) + 1
                idx = _load_ledger()
                ia, ib = idx.get(prev.name), idx.get(ilock.name)
                if ia is not None and ib is not None and ia > ib \
                        and edge not in _exempt_pairs():
                    self.inversions[edge] = \
                        self.inversions.get(edge, 0) + 1
                    new_inversions.append(edge)
            stack.append((ilock, time.perf_counter()))
        m = _get_metrics()
        if m is not None:
            for first, second in new_inversions:
                m["inversions"].inc(first=first, second=second)

    def on_released(self, ilock: "_InstrumentedLock"):
        tid = threading.get_ident()
        span = None
        with self._mu:
            stack = self.held.get(tid, [])
            for i in range(len(stack) - 1, -1, -1):
                if stack[i][0] is ilock:
                    span = time.perf_counter() - stack[i][1]
                    del stack[i]
                    break
            counts = self.holders.get(id(ilock))
            if counts and tid in counts:
                counts[tid] -= 1
                if counts[tid] <= 0:
                    del counts[tid]
                if not counts:
                    del self.holders[id(ilock)]
        if span is not None:
            m = _get_metrics()
            if m is not None:
                m["held"].observe(span, site=ilock.name)

    def on_contention(self, ilock: "_InstrumentedLock"):
        m = _get_metrics()
        if m is not None:
            m["contention"].inc(site=ilock.name)

    # -- waits-for graph ----------------------------------------------

    def set_waiting(self, ilock: "_InstrumentedLock"):
        with self._mu:
            self.waiting[threading.get_ident()] = ilock

    def clear_waiting(self):
        with self._mu:
            self.waiting.pop(threading.get_ident(), None)

    def find_cycle(self) -> Optional[List[dict]]:
        """Follow me -> lock I wait for -> its holder -> lock THAT
        thread waits for -> ... Returns the hop list when the walk
        closes back on the calling thread, else None."""
        start = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        with self._mu:
            hops: List[dict] = []
            tid, seen = start, set()
            while True:
                lk = self.waiting.get(tid)
                if lk is None:
                    return None
                owners = [h for h in self.holders.get(id(lk), {})
                          if h != tid]
                if not owners:
                    return None
                hops.append({
                    "thread": names.get(tid, str(tid)),
                    "waits_for": lk.name,
                    "held": [h.name for h, _t in self.held.get(tid, [])],
                })
                nxt = owners[0]
                if nxt == start:
                    return hops
                if nxt in seen:
                    return None  # a cycle, but not through this thread
                seen.add(nxt)
                tid = nxt

    def on_deadlock(self):
        with self._mu:
            self.deadlocks += 1
        m = _get_metrics()
        if m is not None:
            m["deadlocks"].inc()

    # -- views / reset ------------------------------------------------

    def snapshot_edges(self) -> Dict[Tuple[str, str], int]:
        with self._mu:
            return dict(self.edges)

    def snapshot_inversions(self) -> Dict[Tuple[str, str], int]:
        with self._mu:
            return dict(self.inversions)

    def reset(self):
        """Clear observed state. Only meaningful while no instrumented
        lock is held (tests between cases); holders/waiting are cleared
        too so a leaked lock cannot poison the next case."""
        with self._mu:
            self.holders.clear()
            self.waiting.clear()
            self.held.clear()
            self.edges.clear()
            self.inversions.clear()
            self.deadlocks = 0


_checker = _Checker()


def observed_edges() -> Dict[Tuple[str, str], int]:
    """(first, second) -> times that held->acquired order was seen."""
    return _checker.snapshot_edges()


def observed_inversions() -> List[dict]:
    """Edges contradicting the ledger, with counts and the blessed
    order they violate — the obsdump `locks` inversion list."""
    idx = _load_ledger()
    out = []
    for (first, second), n in sorted(_checker.snapshot_inversions().items()):
        out.append({"first": first, "second": second, "count": n,
                    "ledger_says": f"{second} < {first}",
                    "ledger_index": [idx.get(second), idx.get(first)]})
    return out


def deadlock_count() -> int:
    return _checker.deadlocks


def reset():
    _checker.reset()


# ---------------------------------------------------------------------------
# instrumented primitives
# ---------------------------------------------------------------------------


def _site_from_caller(depth: int = 2) -> str:
    """Fallback site id when the factory caller passed no name."""
    import sys

    try:
        f = sys._getframe(depth)
        return f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno}"
    except (ValueError, AttributeError):
        return "<unknown>"


class _InstrumentedLock:
    """Lock/RLock wrapper: context-manager + acquire/release compatible
    with `threading`'s, feeding the checker on every transition. The
    level-2 blocking path polls the raw lock so it can interleave
    waits-for cycle detection with the wait."""

    def __init__(self, name: str, raw):
        self.name = name
        self._raw = raw

    def acquire(self, blocking: bool = True, timeout: float = -1):
        if self._raw.acquire(False):      # uncontended fast path
            _checker.on_acquired(self)
            return True
        if not blocking:
            return False
        _checker.on_contention(self)
        deadline = (None if timeout is None or timeout < 0
                    else time.monotonic() + timeout)
        detect = level() >= 2
        if not detect and deadline is None:
            self._raw.acquire()           # plain blocking wait
            _checker.on_acquired(self)
            return True
        _checker.set_waiting(self)
        try:
            while True:
                if detect:
                    cycle = _checker.find_cycle()
                    if cycle:
                        _checker.on_deadlock()
                        raise DeadlockError(cycle)
                wait = _POLL_S
                if deadline is not None:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        return False
                    wait = min(wait, left)
                if self._raw.acquire(True, wait):
                    _checker.on_acquired(self)
                    return True
        finally:
            _checker.clear_waiting()

    def release(self):
        _checker.on_released(self)        # while still the owner
        self._raw.release()

    def locked(self):
        return self._raw.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"<lockcheck {type(self._raw).__name__} '{self.name}'>"


class _InstrumentedCondition:
    """Condition sharing an instrumented lock's raw primitive, so
    `with cv:` and `with the_lock:` are ONE identity to the checker
    (mirroring how lockgraph aliases `Condition(self._lock)`
    statically). wait() un-books the hold for its release window and
    re-books on return."""

    def __init__(self, ilock: _InstrumentedLock, name: str):
        self.name = name
        self._ilock = ilock
        self._cond = threading.Condition(ilock._raw)

    def acquire(self, *args, **kwargs):
        return self._ilock.acquire(*args, **kwargs)

    def release(self):
        self._ilock.release()

    def __enter__(self):
        self._ilock.acquire()
        return self

    def __exit__(self, *exc):
        self._ilock.release()
        return False

    def wait(self, timeout: Optional[float] = None):
        _checker.on_released(self._ilock)
        try:
            # lint-exempt:condwait: pass-through wrapper — the CALLER owns the predicate loop
            return self._cond.wait(timeout)
        finally:
            # the stdlib re-acquired the raw lock before returning;
            # deadlocks formed in THAT window are outside our reach
            _checker.on_acquired(self._ilock)

    def wait_for(self, predicate, timeout: Optional[float] = None):
        end = None if timeout is None else time.monotonic() + timeout
        result = predicate()
        while not result:
            left = None
            if end is not None:
                left = end - time.monotonic()
                if left <= 0:
                    break
            self.wait(left)
            result = predicate()
        return result

    def notify(self, n: int = 1):
        self._cond.notify(n)

    def notify_all(self):
        self._cond.notify_all()

    def __repr__(self):
        return f"<lockcheck Condition '{self.name}'>"


# ---------------------------------------------------------------------------
# the factories (what our modules call)
# ---------------------------------------------------------------------------


def Lock(name: Optional[str] = None):
    """threading.Lock at level 0, instrumented wrapper at level >= 1.
    `name` is the canonical site id (match tools/lockgraph.py's
    `<module>.<Class>.<attr>` derivation so the ledger applies)."""
    if level() == 0:
        return threading.Lock()
    return _InstrumentedLock(name or _site_from_caller(), threading.Lock())


def RLock(name: Optional[str] = None):
    if level() == 0:
        return threading.RLock()
    return _InstrumentedLock(name or _site_from_caller(),
                             threading.RLock())


def Condition(lock=None, name: Optional[str] = None):
    """threading.Condition at level 0. At level >= 1 the instrumented
    condition shares `lock`'s identity when `lock` is itself an
    instrumented lock (one site, like the static alias), wraps a raw
    lock under the condition's own name otherwise."""
    if level() == 0:
        return threading.Condition(lock)
    site = name or _site_from_caller()
    if isinstance(lock, _InstrumentedLock):
        ilock = lock
    elif lock is None:
        # stdlib Condition() defaults to an RLock — owners may re-enter
        # (`with cv:` nested under `with cv:`); a plain Lock here would
        # turn that legitimate pattern into a self-deadlock
        ilock = _InstrumentedLock(site, threading.RLock())
    else:
        ilock = _InstrumentedLock(site, lock)
    return _InstrumentedCondition(ilock, site)
