"""Scope + Executor.

Reference: `Scope` (paddle/fluid/framework/scope.h:46) is a hierarchical
name→Variable map; `Executor::Run` (framework/executor.cc:178) interprets a
block op-by-op against it. Here the executor *compiles* the whole program:
scope reads become jit inputs, scope writes become jit outputs
(core/lowering.py), and the compiled step is cached per
(program, feed-signature, fetch-list) — the role of the reference's
ExecutorPrepareContext cache (executor.py:831 program cache).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
import weakref
from collections import OrderedDict
from typing import (Any, Dict, Iterable, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import health as _health
from ..observability import memwatch as _memwatch
from ..observability import perfwatch as _perfwatch
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from . import async_exec, compile_cache, framework, lowering
from . import precision as _precision
from .framework import Program, Variable
from .ir import normalize_dtype
from .places import CPUPlace, Place, default_place

RNG_STATE_VAR = "__rng_state__"


# ---------------------------------------------------------------------------
# Compile introspection
# ---------------------------------------------------------------------------


def _compile_cost(compiled) -> Tuple[Optional[float], Optional[int]]:
    """(flops, output bytes) from an AOT executable's cost/memory
    analysis; either is None when the backend doesn't report it."""
    flops = out_bytes = None
    try:
        ca = compiled.cost_analysis()
        d = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
        if isinstance(d, dict) and d.get("flops", -1) >= 0:
            flops = float(d["flops"])
    except Exception:  # lint-exempt:swallow: cost_analysis is backend-optional introspection
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out_bytes = int(getattr(ma, "output_size_in_bytes", 0))
    except Exception:  # lint-exempt:swallow: memory_analysis is backend-optional introspection
        pass
    return flops, out_bytes


def _executable_cost(compiled) -> Dict[str, Optional[float]]:
    """Retained per-signature cost/memory analysis of an AOT
    executable — the live-MFU numerator (observability/perfwatch.py)
    and the executables line of the HBM attribution
    (observability/memwatch.py). Works on deserialized compile-cache /
    warmstart executables too, so adopted executables are not blind
    spots. Missing fields are None (backend-optional introspection)."""
    flops, out_bytes = _compile_cost(compiled)
    cost: Dict[str, Optional[float]] = {
        "flops": flops, "out_bytes": out_bytes,
        "temp_bytes": None, "code_bytes": None, "arg_bytes": None}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            cost["temp_bytes"] = int(getattr(
                ma, "temp_size_in_bytes", 0))
            cost["code_bytes"] = int(getattr(
                ma, "generated_code_size_in_bytes", 0))
            cost["arg_bytes"] = int(getattr(
                ma, "argument_size_in_bytes", 0))
    except Exception:  # lint-exempt:swallow: memory_analysis is backend-optional introspection
        pass
    return cost


# every _JitDispatch alive in the process: memwatch sums their retained
# generated-code bytes into the `executables` HBM line at sweep time
_live_dispatches: "weakref.WeakSet" = weakref.WeakSet()


def _live_executable_bytes() -> Tuple[int, int]:
    """(generated-code bytes, executable count) over live dispatch
    wrappers' retained signatures — the memwatch executables
    provider."""
    total = count = 0
    for disp in list(_live_dispatches):
        for cost in list(disp._cost_by_sig.values()):
            count += 1
            total += int(cost.get("code_bytes") or 0)
    return total, count


_memwatch.set_executables_provider(_live_executable_bytes)


_JIT_FALLBACK = object()  # sentinel: AOT redispatch failed, use plain jit


def mesh_device_kind(mesh) -> str:
    """device_kind of a jax Mesh's first device — the compile-cache /
    warmstart environment-binding component for sharded executables.
    One definition so compiler.py and spmd_executor.py cannot drift."""
    return getattr(next(iter(mesh.devices.flat), None),
                   "device_kind", "unknown")


class _JitDispatch:
    """A jitted callable that AOT-compiles on first dispatch so the
    compile itself is observable: wall seconds land in
    `paddle_tpu_compile_seconds{kind}`, the executable's cost_analysis()
    FLOPs in `paddle_tpu_compile_flops{kind}`, and a `compile` event in
    the JSONL log. Falls back to the plain jit path — which compiles
    transparently — if AOT lowering fails or a later call's avals drift
    from the compiled signature (jax raises TypeError before executing,
    so donated buffers are untouched).

    With PADDLE_TPU_COMPILE_CACHE set, warm()/first-dispatch consults
    the persistent compile cache (core/compile_cache.py) before
    compiling: a hit deserializes the stored executable (I/O, not XLA),
    a miss compiles and persists for the next process. AOT outcomes are
    remembered PER SIGNATURE (`_tried_sig`): after an AOT failure or a
    signature drift, a warm()/dispatch with new avals retries instead of
    being locked out — a reshaped serving bucket must still get its AOT
    executable.

    `policy` names the precision policy the wrapped computation was
    built under (core/precision.py). It is part of the aval SIGNATURE
    and of the persistent compile-cache fingerprint: a policy flip can
    never be served an executable compiled under the old policy — it
    misses and recompiles instead."""

    # executables already built for a signature, kept so alternating
    # shapes on ONE wrapper (SPMD partial final batch each epoch) swap
    # executables instead of re-paying an AOT compile per alternation
    _AOT_SIG_CAP = 8

    def __init__(self, jit_fn, kind: str, meta: Optional[Dict] = None,
                 policy: Optional[str] = None):
        self._jit = jit_fn
        self._kind = kind
        self._policy = str(policy) if policy else "f32"
        if self._policy != "f32":
            meta = dict(meta or {}, policy=self._policy)
        self._meta = meta
        self._aot = None
        self.aot_error: Optional[BaseException] = None  # last warm() failure
        # how the executable in place got there: "compiled" (XLA),
        # "jax_cache" (JAX's persistent cache returned it), "paddle_cache"
        # (compile_cache.load), "warmstart" (adopt), "remembered" (a
        # signature this wrapper had compiled before)
        self.installed: Optional[str] = None
        self._tried = False
        self._tried_sig = None
        self._aot_by_sig: "OrderedDict[Tuple, Any]" = OrderedDict()
        # retained cost/memory analysis per compiled signature: the
        # live-MFU numerator reads the INSTALLED signature's FLOPs on
        # every recorded step without touching the executable again
        self._cost_by_sig: Dict[Tuple, Dict] = {}
        self._cost_current: Optional[Dict] = None
        self._compile_lock = threading.Lock()
        self._recorded_jit_compiles = 0
        _live_dispatches.add(self)

    def _aval_sig(self, args) -> Tuple:
        """Hashable shape/dtype signature of a warm()/call argument
        tuple — what decides whether a past AOT attempt covers these
        avals. Leads with the precision policy: two executables for the
        same avals under different policies are different programs."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (self._policy, treedef, tuple(
            (tuple(getattr(leaf, "shape", ()) or ()),
             str(getattr(leaf, "dtype", type(leaf).__name__)))
            for leaf in leaves))

    def cache_fingerprint(self, lowered) -> Optional[str]:
        """Persistent compile-cache key for `lowered` under this
        wrapper's precision policy — the policy is key material, so a
        flipped policy always misses instead of deserializing the old
        policy's executable (used by warm() and the serving warmstart
        bake/adopt pair, which must agree byte-for-byte). The default
        f32 policy contributes NO extra key material so f32 keys stay
        byte-identical to the pre-policy (PR 6) keys — upgrading must
        not invalidate every warm cache dir and baked artifact."""
        return compile_cache.fingerprint(
            lowered,
            extra=None if self._policy == "f32" else self._policy)

    def lower(self, *args, **kw):
        return self._jit.lower(*args, **kw)

    def _cache_size(self) -> int:
        """Executables compiled for this callable (AOT + any jit-cache
        fallbacks) — keeps the no-recompile assertions
        (test_step2_recompiles_nothing) meaningful across the AOT path."""
        return (1 if self._aot is not None else 0) + \
            self._jit._cache_size()

    def __getattr__(self, name):
        # only reached for attrs not on the wrapper; avoid recursing if
        # _jit itself is missing (e.g. mid-unpickle)
        return getattr(object.__getattribute__(self, "_jit"), name)

    def warm(self, *args) -> bool:
        """AOT-compile for the given avals (concrete arrays or
        jax.ShapeDtypeStructs) without executing — serving warmup
        compiles every traffic bucket before the first request lands.
        Records the same compile telemetry as a first dispatch; no-op
        once compiled (or once AOT already failed FOR THESE AVALS — a
        new signature retries, so a reshaped bucket can still AOT).
        Consults the persistent compile cache first when enabled: a hit
        installs the deserialized executable and records cache (not
        compile) telemetry, because no XLA compile happened. Returns
        whether an AOT executable is in place. Double-checked lock:
        concurrent first dispatches (HogwildWorker threads on a shared
        executor) must compile ONCE, with the second thread waiting
        rather than jit-compiling a duplicate."""
        sig = self._aval_sig(args)
        if self._tried and sig == self._tried_sig:
            return self._aot is not None
        with self._compile_lock:
            if self._tried and sig == self._tried_sig:
                return self._aot is not None
            remembered = self._aot_by_sig.get(sig)
            if remembered is not None:
                # a signature this wrapper already compiled (drifted
                # away and came back): swap executables, no XLA
                self._aot_by_sig.move_to_end(sig)
                self._aot = remembered
                self._cost_current = self._cost_by_sig.get(sig)
                self._tried, self._tried_sig = True, sig
                self.installed = "remembered"
                return True
            t0 = _tracing.clock()
            aot = None
            try:
                lowered = self._jit.lower(*args)
                key = (self.cache_fingerprint(lowered)
                       if compile_cache.enabled() else None)
                if key:
                    aot = compile_cache.load(key, self._kind)
                if aot is not None:
                    self.installed = "paddle_cache"
                else:
                    aot = lowered.compile()
                    seconds = _tracing.clock() - t0
                    # the request's own row (`compile.requests`): tracing,
                    # lowering, JAX's cache's answer, the backend's time
                    request = _tracing.last_compile_request(t0)
                    self.installed = "jax_cache" if request is not None \
                        and request["cache"] == "hit" else "compiled"
                    flops, out_bytes = _compile_cost(aot)
                    _telemetry.record_compile(self._kind, seconds,
                                              flops=flops,
                                              out_bytes=out_bytes,
                                              meta=self._meta,
                                              request=request)
                    if key:
                        compile_cache.store(key, aot, self._kind)
            except Exception as e:
                # not passed over: the jit path compiles the same program
                # on dispatch and raises the same error there; callers
                # that warm a closed grid at boot (DecodeEngine.warmup)
                # raise it now from aot_error
                aot = None
                self.aot_error = e
                self.installed = None
            self._aot = aot
            if aot is not None:
                self._remember_locked(sig, aot)
            self._tried, self._tried_sig = True, sig
        return self._aot is not None

    def _remember_locked(self, sig, executable):
        """Record sig -> executable + its retained cost/memory analysis
        (caller holds _compile_lock). Cost retention covers every
        install path — fresh compile, persistent-cache hit, warmstart
        adopt — so the live-MFU numerator never goes dark on a path
        that skipped XLA."""
        self._aot_by_sig[sig] = executable
        self._aot_by_sig.move_to_end(sig)
        self._cost_by_sig[sig] = _executable_cost(executable)
        self._cost_current = self._cost_by_sig[sig]
        while len(self._aot_by_sig) > self._AOT_SIG_CAP:
            old, _ = self._aot_by_sig.popitem(last=False)
            self._cost_by_sig.pop(old, None)

    def current_cost(self) -> Optional[Dict]:
        """Cost/memory analysis of the currently installed executable
        (None on the plain-jit fallback path): flops, out_bytes,
        temp_bytes, code_bytes, arg_bytes — fields None when the
        backend doesn't report them."""
        return self._cost_current

    def adopt(self, executable, *args) -> bool:
        """Install a pre-built executable (deserialized from a
        warmstart artifact) as if warm(*args) had just compiled it —
        the serving boot path where even the cache lookup's lowering
        cost is skipped. `args` must be the avals warm() would have
        been called with, so later warm() calls recognize the
        signature as covered."""
        with self._compile_lock:
            self._aot = executable
            self.installed = "warmstart"
            self._tried = True
            self._tried_sig = self._aval_sig(args) if args else None
            if self._tried_sig is not None:
                self._remember_locked(self._tried_sig, executable)
        return True

    def _dispatch_after_drift(self, args):
        """The installed AOT executable raised TypeError/ValueError
        before executing `args` — either signature drift (these avals
        differ from the installed signature) or a genuinely
        incompatible input (e.g. committed to another device;
        _aval_sig ignores placement). Re-resolve an executable for
        THIS call's own signature and run it: a signature this wrapper
        already compiled is an _aot_by_sig dict swap, a new one warms
        through the persistent cache / XLA — so alternating shapes
        (SPMD partial final batch, reshaped serving buckets) never
        re-pay a compile per alternation. Every shared-state decision
        keys on this call's own sig, never the shared _tried_sig:
        concurrent threads (HogwildWorker) drift independently and
        must not evict each other's live executables. Returns
        _JIT_FALLBACK when the signature's own executable fails too —
        after evicting it and latching the signature, so a
        persistently bad executable pays exceptions once, not per
        hot-path call."""
        sig = self._aval_sig(args)
        with self._compile_lock:
            exe = self._aot_by_sig.get(sig)
            if exe is not None:
                self._aot_by_sig.move_to_end(sig)
                self._aot = exe
                self._cost_current = self._cost_by_sig.get(sig)
                self._tried, self._tried_sig = True, sig
        if exe is None and self.warm(*args):
            with self._compile_lock:
                exe = self._aot_by_sig.get(sig)
        if exe is not None:
            try:
                return exe(*args)
            except (TypeError, ValueError):
                with self._compile_lock:
                    self._aot_by_sig.pop(sig, None)
                    if self._tried_sig == sig:
                        self._aot = None
                        self._tried = True
        return _JIT_FALLBACK

    def __call__(self, *args):
        # OOM interceptor: a RESOURCE_EXHAUSTED raised by any dispatch
        # path (AOT, drift re-resolve, plain-jit fallback) dumps the
        # ranked per-owner HBM report + `oom` event before re-raising —
        # free on the happy path (one try frame, no work)
        try:
            return self._dispatch(*args)
        except Exception as e:
            _memwatch.maybe_handle_oom(self._kind, e)
            raise

    def _dispatch(self, *args):
        if not self._tried:
            self.warm(*args)
        elif self._aot is None and self._aval_sig(args) != self._tried_sig:
            # a past AOT failure latched _aot=None at _tried_sig, but
            # THIS call's signature is a different one: re-warm
            # (remembered signatures are a dict swap; cost only lands
            # on the already-degraded path) so one bad signature
            # doesn't strand every other signature's executable on
            # plain jit — the class contract is that new avals retry
            self.warm(*args)
        if self._aot is not None:
            try:
                return self._aot(*args)
            except (TypeError, ValueError):
                # raised before execution: TypeError for aval/dtype
                # mismatch, ValueError for sharding/committed-device
                # mismatch — donated buffers untouched
                out = self._dispatch_after_drift(args)
                if out is not _JIT_FALLBACK:
                    return out
        # jit path: compiles transparently inside the call, so detect a
        # fresh executable via the cache-size growth and time the call —
        # compile-dominated when a compile happened. Keeps
        # paddle_tpu_compiles_total honest after AOT failure/fallback
        # (the recompile-storm signal must not go dark). The high-water
        # mark makes concurrent dispatchers that blocked on the SAME
        # compile record it once, not once per waiting thread.
        t0 = _tracing.clock()
        out = self._jit(*args)
        after = self._jit._cache_size()
        if after > self._recorded_jit_compiles:
            with self._compile_lock:
                if after > self._recorded_jit_compiles:
                    self._recorded_jit_compiles = after
                    _telemetry.record_compile(
                        self._kind, _tracing.clock() - t0,
                        meta=dict(self._meta or {}, jit_fallback=True),
                        request=_tracing.last_compile_request(t0))
        return out


def _health_scan(site: str, named_values, level: int):
    """Device-side prefilter in front of health.check_numerics: reduce
    isfinite (and the optional |x| threshold) ON DEVICE so the per-step
    cost is one scalar transfer per float var — only arrays that are
    actually suspect get downloaded to host for nan/inf classification.
    (The pre-health FLAGS_check_nan_inf code had the same shape; the
    health layer keeps the counting/event/raise semantics.)"""
    suspects = []
    thresh = _health.max_abs()
    for n, v in named_values:
        if v is None:
            continue
        try:
            arr = jnp.asarray(v)
        except (TypeError, ValueError):
            continue
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            continue
        bad = not bool(jnp.isfinite(arr).all())
        if not bad and thresh is not None and arr.size:
            bad = bool(jnp.abs(arr).max() > thresh)
        if bad:
            suspects.append((n, v))
    # always called (even with no suspects) so the sweep counter ticks
    _health.check_numerics(site, suspects, level=level)


def _post_step_health(writes, fetch_names, fetches, scope):
    """Shared post-step epilogue for Executor.run / run_chained /
    CompiledProgram._run: resolve the check level (legacy
    FLAGS_check_nan_inf forces raise semantics), scan written states +
    fetches, and sample the device-memory gauge. One definition so the
    level semantics and scan sites cannot drift between run paths."""
    from .flags import get_flag

    level = 2 if get_flag("FLAGS_check_nan_inf") \
        else _health.check_level()
    if level:
        _health_scan("executor_state",
                     ((n, scope.find_var(n)) for n in writes), level)
        _health_scan("executor_fetch", zip(fetch_names, fetches), level)
    if _health.introspection_enabled():
        _record_live_device_memory()


_MEM_SWEEP_MIN_INTERVAL_S = 5.0
_last_mem_sweep = [0.0]  # monotonic seconds of the last live_arrays walk


def _record_live_device_memory():
    """Gauge live device-buffer bytes. Only called when observability
    is enabled (health.introspection_enabled), and rate-limited: the
    sweep walks every live jax.Array, which on a big model costs more
    per step than any scraper can use — gauges are sampled on
    seconds-scale intervals anyway. The walk itself lives in
    observability/memwatch.py, which attributes each buffer to its
    registered owner (KV pool, params, optimizer state, other) and
    keeps the legacy paddle_tpu_device_live_bytes totals in sync."""
    now = time.monotonic()
    if now - _last_mem_sweep[0] < _MEM_SWEEP_MIN_INTERVAL_S:
        return
    _last_mem_sweep[0] = now
    _memwatch.sweep(force=True)


class Scope:
    """Hierarchical variable store (reference: framework/scope.h:46)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self.parent = parent
        self.kids: List[Scope] = []

    def var(self, name: str):
        if name not in self._vars:
            self._vars[name] = None
        return self._vars[name]

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def set_var(self, name: str, value):
        self._vars[name] = value

    def erase(self, names: Sequence[str]):
        for n in names:
            self._vars.pop(n, None)

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self.kids.append(kid)
        return kid

    def drop_kids(self):
        self.kids.clear()

    def local_var_names(self) -> List[str]:
        return list(self._vars)

    # numpy convenience used everywhere in tests
    def get(self, name: str) -> np.ndarray:
        v = self.find_var(name)
        if v is None:
            raise KeyError(f"variable '{name}' not found in scope")
        return np.asarray(v)


_global_scope = Scope()
_scope_stack: List[Scope] = []


def global_scope() -> Scope:
    return _scope_stack[-1] if _scope_stack else _global_scope


@contextlib.contextmanager
def scope_guard(scope: Scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def _as_fetch_name(f) -> str:
    if isinstance(f, Variable):
        return f.name
    return str(f)


@functools.lru_cache(maxsize=None)
def _canonical_dtype_cached(want: str, x64: bool) -> np.dtype:
    from jax import dtypes as _jdt

    del x64  # part of the cache key only: canonicalization depends on it
    return np.dtype(_jdt.canonicalize_dtype(np.dtype(want)))


def _canonical_dtype(want) -> np.dtype:
    """Feed-normalization target dtype, canonicalized to jax's x64
    state. Without this, an int64-declared feed under 32-bit jax costs
    an astype (plus a truncation warning) EVERY step on the hot path,
    only for jnp to hand back int32 anyway. Cached per (dtype, x64
    flag) — this runs once per feed var per step on every run path."""
    return _canonical_dtype_cached(np.dtype(want).str,
                                   bool(jax.config.jax_enable_x64))


# run_stream unrolls its windows (straight-line XLA ~2x a rolled scan
# on CPU conv bodies) only up to this size — unroll compile time grows
# with n_steps, and past this the amortization no longer pays for it.
_UNROLL_WINDOW_MAX = 32


def _chained_cache_limit() -> int:
    """Per-program bound on cached chained executables (PADDLE_TPU_
    CHAINED_CACHE, default 8): every (n_steps, per_step_feeds) key is a
    full XLA executable, so an unbounded map under a driver that varies
    its window size is a memory leak with a compile bill attached."""
    raw = os.environ.get("PADDLE_TPU_CHAINED_CACHE")
    if not raw:
        return 8
    try:
        return max(1, int(raw))
    except ValueError:
        return 8


def _feed_signature(feed: Dict[str, Any]) -> Tuple:
    """Shape/dtype signature of a feed dict — what decides whether two
    per-step feeds can share a stacked window / compiled step."""
    return tuple(sorted(
        (k, tuple(getattr(v, "shape", ())),
         str(getattr(v, "dtype", type(v).__name__)))
        for k, v in feed.items()))


def _stack_feed_window(feeds: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Collate same-signature per-step feeds with a leading [n] axis.
    Host-resident windows take one memcpy + ONE transfer at dispatch
    (np.stack) instead of K per-item transfers + a device concat;
    device-resident (prefetched) values stay on device (jnp.stack)."""
    def _stack(vals):
        if all(isinstance(v, np.ndarray) for v in vals):
            return np.stack(vals)
        return jnp.stack(vals)

    return {k: _stack([f[k] for f in feeds]) for k in feeds[0]}


def _pre_run_validate(program: Program, feed_names, fetch_names,
                      policy, where: str):
    """Env-gated static analysis in front of every run path
    (PADDLE_TPU_VALIDATE=0|1|2 — off/warn/error; paddle_tpu/analysis).
    The env probe keeps the default hot path at one dict lookup and the
    analysis package entirely unimported; when enabled, results are
    cached per (program version, run signature) so a steady-state loop
    pays for exactly one walk."""
    if not os.environ.get("PADDLE_TPU_VALIDATE"):
        return
    from ..analysis import maybe_validate

    maybe_validate(program, feed_names=feed_names,
                   fetch_names=fetch_names, policy=policy, where=where)


def _normalize_feed(program: Program, feed: Dict[str, Any],
                    policy: Optional["_precision.PrecisionPolicy"] = None
                    ) -> Dict[str, Any]:
    """Feed normalization shared by every run path (Executor._lookup_
    step, CompiledProgram._run, SPMDRunner.run): device-transfer via
    jnp.asarray and cast to the var's declared dtype, canonicalized to
    jax's x64 state — except that under a non-f32 precision policy
    FLOATING feeds target the policy's compute dtype instead of the
    declared one. That kills the silent upcast on the stream hot path:
    a bf16 feed under a bf16/mixed_bf16 policy already matches the
    target and is passed through with no astype at all."""
    if policy is None:
        policy = _precision.resolve(program)
    norm_feed = {}
    for name, val in feed.items():
        vdesc = None
        for b in program.desc.blocks:
            if name in b.vars:
                vdesc = b.vars[name]
                break
        arr = jnp.asarray(val)
        if vdesc is not None:
            want = policy.feed_dtype(
                _canonical_dtype(normalize_dtype(vdesc.dtype)))
            if arr.dtype != want:
                arr = arr.astype(want)
        norm_feed[name] = arr
    return norm_feed


def _finish_fetches(fetches, return_numpy: bool, sync: bool,
                    site: str = "executor"):
    """Shared fetch epilogue for every run path. sync=False wraps the
    device arrays in a lazy FetchHandle (nothing touches the host until
    .result()). sync=True with return_numpy forces the classic
    synchronous fetch — instrumented as host-blocked time, which is
    exactly the per-step round trip the async paths exist to hide.
    return_numpy=False returns the device arrays untouched."""
    if not sync:
        return async_exec.FetchHandle(fetches, site=site)
    if not return_numpy:
        return list(fetches)
    t0 = time.perf_counter()
    try:
        jax.block_until_ready(fetches)
    except Exception as e:  # lint-exempt:swallow: non-array fetches (rare lowering paths) convert below
        # an async device OOM surfaces HERE, not at dispatch: dump the
        # forensics before the conversion below re-raises it
        _memwatch.maybe_handle_oom(site, e)
    out = [np.asarray(f) for f in fetches]
    _telemetry.record_host_blocked("executor_sync",
                                   time.perf_counter() - t0, stall=False)
    return out


class _CompiledStep:
    """One jitted program specialization, built under ONE precision
    policy: a pure-bf16 policy casts floating state to the compute
    dtype at step entry (inside the jit — params stay bf16 on device
    thereafter, so the cast is a one-time signature transition), a
    mixed policy activates the lowering-time op autocast instead and
    leaves master state f32."""

    def __init__(self, program: Program, feed_names: Tuple[str, ...],
                 fetch_names: Tuple[str, ...], is_test: bool,
                 policy: Optional["_precision.PrecisionPolicy"] = None):
        desc = program.desc
        policy = policy if policy is not None \
            else _precision.resolve(program)
        self.policy = policy
        reads, writes = lowering.analyze_state_vars(desc, set(feed_names))
        persistable = {
            v.name
            for b in desc.blocks
            for v in b.vars.values()
            if v.persistable
        }
        for n in fetch_names:
            if n in persistable and n not in reads and n not in writes:
                reads.append(n)
        self.const_reads = tuple(n for n in reads if n not in writes)
        self.mut_reads = tuple(n for n in reads if n in writes)
        self.writes = tuple(writes)
        self.fetch_names = fetch_names
        self.feed_names = feed_names

        def step(feeds, const_states, mut_states, rng):
            env = dict(const_states)
            env.update(mut_states)
            env.update(feeds)
            if policy.cast_state:
                # pure low-precision: state joins the compute width; the
                # first step's f32->bf16 casts compile once, thereafter
                # the scope holds bf16 arrays and the cast is a no-op
                env = {k: _precision.cast_floating(v, policy.compute_dtype)
                       for k, v in env.items()}
            step_key, new_rng = jax.random.split(rng)
            with _precision.autocast(policy):
                lowering.lower_block(desc, 0, env, rng_key=step_key,
                                     is_test=is_test)
            fetches = []
            for n in fetch_names:
                if n not in env:
                    raise lowering.LoweringError(
                        f"fetch var '{n}' was not produced by the program")
                fetches.append(env[n])
            new_states = {n: env[n] for n in self.writes if n in env}
            return fetches, new_states, new_rng

        # mut_states (param updates) are donated: in-place on device, the
        # reference's overwrite-in-scope semantics without a copy.
        self._step = step
        self.fn = _JitDispatch(
            jax.jit(step, donate_argnums=(2,)), "step",
            meta={"fetches": len(fetch_names), "writes": len(writes)},
            policy=policy.name)
        # LRU-bounded: each entry is a whole XLA executable (see
        # _chained_cache_limit); evictions are counted in the registry.
        # Key: (n_steps, per_step_feeds, unroll).
        self._chained: "OrderedDict[Tuple[int, bool, bool], Any]" = \
            OrderedDict()
        self._last_chained_fn: Optional[_JitDispatch] = None

    def chained_cost(self) -> Optional[Dict]:
        """Retained cost analysis of the last chained dispatch used by
        run_chained — note its FLOPs cover the WHOLE n_steps window,
        matching the one wall-time window run_chained records."""
        fn = self._last_chained_fn
        return fn.current_cost() if fn is not None else None

    def chained_fn(self, n_steps: int, per_step_feeds: bool = False,
                   unroll="auto", platform: Optional[str] = None):
        """n_steps program iterations scan-chained in ONE executable.
        Amortizes the fixed per-invocation dispatch cost so
        repeated-step timing measures framework+compute. With
        per_step_feeds, each feed carries a leading [n_steps] axis and
        the scan consumes one slice per iteration — a whole data chunk
        trains in ONE dispatch (the fast path under
        train_from_dataset's batch loop). Reference analogue: the C++
        executor's prepared-context replay loop (executor.py:418
        ExecutorPrepareContext).

        `unroll` unrolls the scan body: XLA optimizes the window as
        straight-line code (on CPU a conv inside the rolled while-loop
        runs ~2x slower than the same conv inlined), trading compile
        time proportional to n_steps. The streaming driver uses it for
        its small windows. "auto" resolves per backend: unrolled on CPU
        (up to _UNROLL_WINDOW_MAX — the rolled while-loop is slower
        per step there, reproduced by a pure-jax control, so it is
        opt-in), rolled elsewhere (one bounded compile, no CPU penalty
        applies)."""
        if unroll == "auto":
            # resolve against the EXECUTING device's platform when the
            # caller supplies it (run_chained passes the place's) — a
            # CPUPlace executor on a TPU-default host must still get
            # the unrolled CPU path
            unroll = ((platform or jax.default_backend()) == "cpu"
                      and n_steps <= _UNROLL_WINDOW_MAX)
        key = (n_steps, per_step_feeds, bool(unroll))
        fn = self._chained.get(key)
        if fn is not None:
            self._chained.move_to_end(key)
            return fn
        step = self._step
        mut_keys = set(self.mut_reads)

        def chained(feeds, const_states, mut_states, rng):
            def split(new_states, mut):
                merged = dict(mut)
                merged.update({k: v for k, v in new_states.items()
                               if k in mut_keys})
                rest = {k: v for k, v in new_states.items()
                        if k not in mut_keys}
                return merged, rest

            def feeds_at(i):
                if not per_step_feeds:
                    return feeds
                return {k: v[i] for k, v in feeds.items()}

            # step 1 runs outside the scan: write-only states don't exist
            # before it, and the scan carry needs their fixed structure.
            # Carrying them (instead of stacking as scan ys) keeps memory
            # O(1) in n_steps — only the final value is observable in the
            # scope, exactly like sequential execution.
            fetches0, new0, rng1 = step(feeds_at(0), const_states,
                                        mut_states, rng)
            mut1, rest1 = split(new0, mut_states)

            def body(carry, i):
                mut, rest, r = carry
                del rest  # fully replaced: new_rest has the same key set
                fetches, new_states, new_r = step(feeds_at(i),
                                                  const_states, mut, r)
                merged, new_rest = split(new_states, mut)
                return (merged, new_rest, new_r), fetches

            (mut_f, rest_f, rng_f), ys = jax.lax.scan(
                body, (mut1, rest1, rng1),
                jnp.arange(1, n_steps), length=n_steps - 1,
                unroll=bool(unroll))
            stacked = jax.tree_util.tree_map(
                lambda f0, fs: jnp.concatenate([f0[None], fs]),
                fetches0, ys)
            new_states = dict(mut_f)
            new_states.update(rest_f)
            return stacked, new_states, rng_f

        # donate mut_states AND the rng key: together with `rest`
        # (created inside) that is the whole scan carry, so XLA can
        # alias every carry component in place of an input buffer
        fn = _JitDispatch(
            jax.jit(chained, donate_argnums=(2, 3)), "chained",
            meta={"n_steps": int(n_steps),
                  "per_step_feeds": bool(per_step_feeds),
                  "unroll": bool(unroll)},
            policy=self.policy.name)
        self._chained[key] = fn
        limit = _chained_cache_limit()
        while len(self._chained) > limit:
            self._chained.popitem(last=False)
            _telemetry.record_chained_eviction()
        return fn

    def run_chained(self, scope: Scope, feed: Dict[str, Any], rng,
                    n_steps: int, per_step_feeds: bool = False,
                    unroll=False, platform: Optional[str] = None):
        """Like __call__ but n_steps scan-chained; fetches come back
        stacked along a leading [n_steps] axis. With per_step_feeds,
        each feed value carries its own leading [n_steps] axis and step
        i consumes slice i. unroll="auto" picks per backend (see
        chained_fn); on CPU with n_steps beyond the unroll cap the run
        is split into unrolled windows instead of rolling the scan."""
        plat = platform or jax.default_backend()
        if unroll == "auto" and plat == "cpu" \
                and n_steps > _UNROLL_WINDOW_MAX:
            return self._run_chained_windowed(scope, feed, rng, n_steps,
                                              per_step_feeds)
        const_states, mut_states = self._gather_states(scope)
        fn = self.chained_fn(n_steps, per_step_feeds, unroll,
                             platform=plat)
        self._last_chained_fn = fn
        fetches, new_states, new_rng = fn(feed, const_states,
                                          mut_states, rng)
        for n, v in new_states.items():
            scope.set_var(n, v)
        return fetches, new_rng

    def _run_chained_windowed(self, scope: Scope, feed, rng,
                              n_steps: int, per_step_feeds: bool):
        """CPU fallback for big chained runs: XLA-CPU executes convs
        inside a rolled while-loop slower than straight-line code (a
        pure-jax loop-vs-scan control reproduces it, so it is the
        backend, not lost donation), so n_steps is split into
        <=_UNROLL_WINDOW_MAX unrolled windows — identical sequential semantics and rng
        stream, a handful of dispatches instead of one (dispatch
        overhead on CPU is microseconds)."""
        out_chunks: Optional[List[List[Any]]] = None
        done = 0
        while done < n_steps:
            n = min(_UNROLL_WINDOW_MAX, n_steps - done)
            chunk = feed if not per_step_feeds else \
                {k: v[done:done + n] for k, v in feed.items()}
            const_states, mut_states = self._gather_states(scope)
            fn = self.chained_fn(n, per_step_feeds, True)
            self._last_chained_fn = fn
            fetches, new_states, rng = fn(chunk, const_states,
                                          mut_states, rng)
            for name, v in new_states.items():
                scope.set_var(name, v)
            if out_chunks is None:
                out_chunks = [[f] for f in fetches]
            else:
                for lst, f in zip(out_chunks, fetches):
                    lst.append(f)
            done += n
        fetches = [jnp.concatenate(ch) if len(ch) > 1 else ch[0]
                   for ch in (out_chunks or [])]
        return fetches, rng

    def _gather_states(self, scope: Scope):
        const_states = {}
        for n in self.const_reads:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"variable '{n}' is read by the program but missing from "
                    f"the scope — run the startup program first")
            const_states[n] = v
        mut_states = {}
        for n in self.mut_reads:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"variable '{n}' is updated in place but missing from the "
                    f"scope — run the startup program first")
            mut_states[n] = v
        return const_states, mut_states

    def __call__(self, scope: Scope, feed: Dict[str, Any], rng):
        const_states, mut_states = self._gather_states(scope)
        fetches, new_states, new_rng = self.fn(feed, const_states, mut_states, rng)
        for n, v in new_states.items():
            scope.set_var(n, v)
        return fetches, new_rng


# the cache-entries gauge promises a process-wide total, not the count of
# whichever executor ran last; the lock keeps hot-path iteration safe
# against a concurrent Executor() construction in another thread
_live_executors: "weakref.WeakSet[Executor]" = weakref.WeakSet()
_live_executors_lock = threading.Lock()


class Executor:
    """reference: python/paddle/fluid/executor.py:418."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or default_place()
        self._cache: Dict[Any, _CompiledStep] = {}
        self._cache_hits = 0
        self._cache_misses = 0
        self._dev_kind: Optional[str] = None
        with _live_executors_lock:
            _live_executors.add(self)

    def _device_kind(self) -> str:
        """device_kind of this executor's place — the live-MFU peak
        lookup key (observability/device_peaks.py). Cached: the place
        never changes after construction."""
        if self._dev_kind is None:
            self._dev_kind = getattr(self.place.jax_device(),
                                     "device_kind", "unknown")
        return self._dev_kind

    def close(self):
        self._cache.clear()

    def cache_stats(self) -> Dict[str, int]:
        """Program-cache behavior, observable for benchmarks/tests: after
        the first run of a (program, feed-signature) pair every later
        run must be a hit — step 2+ retraces/recompiles nothing. The same
        events feed the process-wide registry
        (paddle_tpu_executor_cache_total in observability.snapshot());
        this per-instance view stays for single-executor assertions."""
        return {"hits": self._cache_hits, "misses": self._cache_misses,
                "entries": len(self._cache)}

    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        sync: bool = True,
    ):
        """One program step. sync=False returns a FetchHandle — the
        device arrays stay put and the host moves on immediately;
        .result() resolves to numpy on demand (async_exec). With
        sync=True, return_numpy=False likewise returns the device
        arrays untouched so callers can stay async by hand."""
        # CompiledProgram carries its own sharded run path (core/compiler.py).
        from .compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            return program._run(self, feed, fetch_list, scope,
                                return_numpy, sync=sync)

        program = program if program is not None else framework.default_main_program()
        scope = scope if scope is not None else global_scope()
        feed = dict(feed or {})
        fetch_names = tuple(_as_fetch_name(f) for f in (fetch_list or []))

        # pserver program: a single listen_and_serv op — run the host server
        # loop, blocking like the reference (listen_and_serv_op.cc)
        ops0 = program.desc.block(0).ops
        if len(ops0) == 1 and ops0[0].type == "listen_and_serv":
            from ..ps.server import ParameterServer, snapshot_config_from_env

            a = ops0[0].attrs
            server = ParameterServer(
                a["endpoint"], int(a["num_trainers"]),
                mode=a.get("mode", "sync"),
                dc_asgd_lambda=float(a.get("dc_asgd_lambda", 0.0)),
                # PADDLE_TPU_PS_SNAPSHOT_DIR et al: a respawned server
                # restores its committed tables instead of reinitializing
                **snapshot_config_from_env(a["endpoint"]))
            server.serve_forever()  # blocks until shutdown request
            return []

        with _telemetry.executor_step("run") as rec:
            step, norm_feed = self._lookup_step(program, feed, fetch_names,
                                                use_program_cache)
            rec.set_feed(norm_feed)
            rng = self._get_rng(scope, program)
            # step_span: joins the ambient trace when one is active and
            # STARTS one (head-sampled) when PADDLE_TPU_TRACE_SAMPLE is
            # armed — the training path's trace origin, so PS RPCs
            # issued inside the step inherit the step's trace id
            with _tracing.step_span("executor.run", cat="step",
                                    fetches=len(fetch_names)):
                with jax.default_device(self.place.jax_device()):
                    fetches, new_rng = step(scope, norm_feed, rng)
            scope.set_var(RNG_STATE_VAR, new_rng)
            # after execution: the dispatch wrapper has compiled by now,
            # so current_cost() carries this signature's retained FLOPs
            rec.set_perf("step", step.fn.current_cost(),
                         device_kind=self._device_kind())

            # reference: FLAGS_check_nan_inf (flags.cc:44). The legacy
            # flag forces raise-level checking; PADDLE_TPU_CHECK_NUMERICS
            # selects warn (1) or raise (2). Both route through the
            # health layer so anomalies are counted, logged as events,
            # and flip /healthz — the flag's raise semantics (and its
            # post-step scan of every written state + fetch) are kept.
            _post_step_health(step.writes, fetch_names, fetches, scope)

            return _finish_fetches(fetches, return_numpy, sync,
                                   site="executor")

    def _lookup_step(self, program: Program, feed: Dict[str, Any],
                     fetch_names: Tuple[str, ...], use_program_cache: bool):
        """Normalize feeds and resolve the compiled step from the program
        cache, keyed by (program identity+version, feed shapes/dtypes,
        fetches, mode, PRECISION POLICY) — the reference's
        ExecutorPrepareContext cache (executor.py:418/831). The policy
        is resolved once here (program attr > PADDLE_TPU_PRECISION >
        f32) and baked into both the feed normalization and the
        compiled step, so a policy flip re-keys instead of reusing the
        old width's executable."""
        policy = _precision.resolve(program)
        norm_feed = _normalize_feed(program, feed, policy)
        _pre_run_validate(program, tuple(norm_feed), fetch_names, policy,
                          where="executor")
        feed_sig = tuple(sorted((k, tuple(v.shape), str(v.dtype)) for k, v in norm_feed.items()))
        key = (id(program), program._version, feed_sig, fetch_names,
               program._is_test, policy.name)
        step = self._cache.get(key) if use_program_cache else None
        hit = step is not None
        if step is None:
            self._cache_misses += 1
            step = _CompiledStep(program, tuple(norm_feed), fetch_names,
                                 program._is_test, policy=policy)
            if use_program_cache:
                self._cache[key] = step
        else:
            self._cache_hits += 1
        with _live_executors_lock:
            entries = sum(len(e._cache) for e in _live_executors)
        _telemetry.record_cache_event(hit=hit, entries=entries)
        return step, norm_feed

    def run_chained(self, program=None, feed=None, fetch_list=None,
                    n_steps=1, scope=None, return_numpy=True,
                    per_step_feeds=False, sync=True, unroll="auto"):
        """Run `program` n_steps times inside one jitted lax.scan — the
        cached-executable fast path: a single dispatch covers n_steps
        iterations, so per-step overhead is framework+compute time
        rather than the per-invocation host round trip. With
        per_step_feeds, every feed value
        carries a leading [n_steps] axis and step i trains on slice i
        (a whole data chunk per dispatch — the fast path under a batch
        loop); otherwise the same feeds repeat. Scope state afterwards
        matches n_steps sequential `run` calls; each fetch comes back
        stacked with a leading [n_steps] axis.

        `unroll` defaults to "auto": on CPU the scan body is unrolled
        (or, past _UNROLL_WINDOW_MAX steps, windowed into unrolled
        chunks) because XLA-CPU runs the rolled while-loop slower per
        step; on TPU/GPU it stays a rolled scan — ONE dispatch, bounded
        compile time. Pass unroll=False explicitly to
        opt back into the rolled scan everywhere."""
        if int(n_steps) < 1:
            raise ValueError(f"run_chained needs n_steps >= 1, got "
                             f"{n_steps}")
        program = program if program is not None \
            else framework.default_main_program()
        scope = scope if scope is not None else global_scope()
        fetch_names = tuple(_as_fetch_name(f) for f in (fetch_list or []))
        feed = dict(feed or {})
        if per_step_feeds:
            for name, val in feed.items():
                # shape only — np.asarray would force a device-to-host
                # copy of the whole chunk on the very path built to
                # avoid host round trips
                shape = getattr(val, "shape", None)
                if shape is None:
                    shape = np.asarray(val).shape  # lists etc.
                if tuple(shape[:1]) != (int(n_steps),):
                    raise ValueError(
                        f"per_step_feeds: feed '{name}' needs a leading "
                        f"[{n_steps}] axis, got shape {tuple(shape)}")
        with _telemetry.executor_step("chained") as rec:
            step, norm_feed = self._lookup_step(program, feed, fetch_names,
                                                True)
            rec.set_feed(norm_feed)
            rng = self._get_rng(scope, program)
            # step_span: trace origin for the chained/stream fast path
            # (run_stream windows flush through here)
            with _tracing.step_span("executor.run_chained", cat="step",
                                    n_steps=int(n_steps)):
                with jax.default_device(self.place.jax_device()):
                    fetches, new_rng = step.run_chained(
                        scope, norm_feed, rng, int(n_steps),
                        per_step_feeds=bool(per_step_feeds),
                        unroll=unroll,
                        platform=getattr(self.place.jax_device(),
                                         "platform", None))
            scope.set_var(RNG_STATE_VAR, new_rng)
            rec.set_perf("chained", step.chained_cost(),
                         device_kind=self._device_kind())
            _post_step_health(step.writes, fetch_names, fetches, scope)
            return _finish_fetches(fetches, return_numpy, sync,
                                   site="chained")

    def run_stream(self, program=None, feed_iter: Optional[Iterable] = None,
                   fetch_list=None, window: int = 8, scope=None,
                   in_flight: int = async_exec.DEFAULT_IN_FLIGHT):
        """Streaming driver: consume an ITERATOR of per-step feed dicts
        and yield one lazy FetchHandle per window of up to `window`
        micro-chained steps — the cached-executable amortization of
        run_chained without requiring all feeds pre-stacked up front.

        Feeds are buffered until the window fills (or the feed
        signature changes — e.g. a short final batch — or the iterator
        ends), host-collated with a leading [n] axis, and dispatched as
        ONE chained executable with per_step_feeds=True. Each yielded
        handle carries `.start_step`/`.n_steps`; its `.result()` is the
        stacked fetch list. A bounded InFlightWindow (`in_flight`,
        default 2) resolves the oldest handle before admitting a new
        one, so no more than `in_flight` windows of fetch buffers are
        ever device-resident; the remainder are drained when the
        generator closes. Feeds may already be device arrays (a
        DevicePrefetcher upstream) — collation then stays on device.

        Scope state after exhaustion matches per-step `run` calls; see
        RESILIENCE.md for the window-boundary semantics the
        fault-tolerant drivers layer on top."""
        if feed_iter is None:
            raise ValueError("run_stream needs a feed iterator")
        program = program if program is not None \
            else framework.default_main_program()
        scope = scope if scope is not None else global_scope()
        window = max(1, int(window))
        win = async_exec.InFlightWindow(limit=in_flight, site="stream")

        def gen():
            buf: List[Dict[str, Any]] = []
            sig = None
            step0 = 0

            def flush():
                nonlocal buf, step0
                feeds, buf = buf, []
                n = len(feeds)
                stacked = _stack_feed_window(feeds)
                # the explicit reserve is load-bearing: it must run
                # BEFORE run_chained creates the new handle, or
                # limit+1 windows of buffers coexist transiently
                # (admit's own reserve would fire too late)
                win.reserve()
                h = self.run_chained(program, feed=stacked,
                                     fetch_list=fetch_list, n_steps=n,
                                     per_step_feeds=True, scope=scope,
                                     sync=False,
                                     unroll=n <= _UNROLL_WINDOW_MAX)
                h.start_step, h.n_steps = step0, n
                step0 += n
                return win.admit(h)

            try:
                for feed in feed_iter:
                    feed = dict(feed)
                    s = _feed_signature(feed)
                    if buf and s != sig:
                        yield flush()
                    sig = s
                    buf.append(feed)
                    if len(buf) >= window:
                        yield flush()
                if buf:
                    yield flush()
            finally:
                # resolve stragglers so device fetch buffers free even
                # when the consumer abandons the stream mid-way
                win.drain()

        return gen()

    def _get_rng(self, scope: Scope, program: Program):
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            seed = program.random_seed or framework.global_seed()
            rng = jax.random.key(seed)
            scope.set_var(RNG_STATE_VAR, rng)
        return rng

    # ------------------------------------------------------------------
    # Dataset entry points (reference: executor.py train_from_dataset) are
    # provided by paddle_tpu.trainer; thin delegation keeps API parity.
    # ------------------------------------------------------------------

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from ..trainer import train_from_dataset

        return train_from_dataset(self, program, dataset, scope, thread, debug,
                                  fetch_list, fetch_info, print_period)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from ..trainer import infer_from_dataset

        return infer_from_dataset(self, program, dataset, scope, thread, debug,
                                  fetch_list, fetch_info, print_period)
