"""SPMD (per-device-graph) program execution — the collective-transpiler
runtime.

Reference execution model: transpiler/collective.py rewrites the single-device
program with explicit c_allreduce ops, then EACH process runs its own graph
and the collectives synchronize (multi-process NCCL2 mode, SURVEY §2.5).

TPU-native: one process runs the program under jax.shard_map with the 'dp'
axis manual — each device traces the same op sequence on its batch shard, and
the program's explicit collective ops (ops/collective.py) lower to real
lax.psum/all_gather over the axis. This is the runtime that makes the c_*
collective op family first-class (under plain pjit GSPMD they'd be
redundant)."""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import health as _health
from ..observability import perfwatch as _perfwatch
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from ..core import framework, lowering
from ..core import precision as _precision
from ..core.executor import (RNG_STATE_VAR, Scope, _as_fetch_name,
                             _finish_fetches, _JitDispatch, _health_scan,
                             mesh_device_kind, _normalize_feed,
                             _record_live_device_memory, global_scope)
from ..core.framework import Program


def _repatriate(v, mesh, mesh_devs):
    """Move a value committed to devices OUTSIDE `mesh` back under it.
    After an elastic resize (SPMDRunner.resize), persistable state and
    the rng var in the scope were written by the old-mesh executable
    and live on the old device set — dispatching them into the new
    mesh's shard_map would fail with an incompatible-devices error.
    Replicated re-placement is correct here because SPMD state vars and
    the rng are replicated by construction (in_specs P()).

    Only values carrying a NamedSharding on a DIFFERENT mesh move:
    single-device/default placements were always accepted by jit (the
    pre-elastic behavior, kept untouched and transfer-free), while an
    old-mesh NamedSharding fails jit's committed-device consistency
    check in BOTH directions — scale-in (old set ⊃ new) and scale-out
    (old set ⊂ new) alike, hence mesh equality, not subset. `mesh_devs`
    is the mesh's frozenset of devices, precomputed by the caller; the
    `sharding.mesh is mesh` fast path is the common case (state written
    back by THIS mesh's executable) and runs per state var per step."""
    sharding = getattr(v, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return v
    if sharding.mesh is mesh or \
            frozenset(sharding.mesh.devices.flat) == mesh_devs:
        return v
    return jax.device_put(v, NamedSharding(mesh, P()))


class SPMDRunner:
    """Run a (collective-transpiled) Program with the 'dp' axis manualized.

    Feeds are split on dim 0 across 'dp'; persistable state is replicated.
    Fetches are averaged over devices unless reduce='first'.
    """

    def __init__(self, program: Program, mesh: Mesh, axis: str = "dp",
                 reduce: str = "mean"):
        self.program = program
        self.mesh = mesh
        self.axis = axis
        self.reduce = reduce
        self._cache: Dict[Any, Any] = {}
        self._mesh_devs = frozenset(mesh.devices.flat)

    def resize(self, mesh: Mesh) -> "SPMDRunner":
        """Point the runner at a re-formed mesh (elastic scale-in/out).
        Compiled steps capture the mesh at build time, so the step
        cache is dropped whenever the mesh object changes; returning to
        a PREVIOUS world size re-pays only compile-cache I/O, not a
        fresh XLA compile (PR 6's persistent cache keys on the lowered
        module, which embeds the mesh shape)."""
        if mesh is not self.mesh:
            self.mesh = mesh
            self._mesh_devs = frozenset(mesh.devices.flat)
            self._cache.clear()
        return self

    def run(self, executor, feed=None, fetch_list=None, scope: Optional[Scope] = None,
            return_numpy: bool = True, sync: bool = True):
        # timer covers feed normalization + cache lookup + dispatch,
        # matching Executor.run's span
        t0 = time.perf_counter()
        host0 = _telemetry.host_blocked_total()
        program = self.program
        scope = scope if scope is not None else global_scope()
        feed = dict(feed or {})
        fetch_names = tuple(_as_fetch_name(f) for f in (fetch_list or []))

        policy = _precision.resolve(program)
        norm_feed = _normalize_feed(program, feed, policy)
        sig = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                           for k, v in norm_feed.items()))
        key = (program._version, sig, fetch_names, policy.name)
        step = self._cache.get(key)
        if step is None:
            step = self._build(tuple(norm_feed), fetch_names, policy)
            self._cache[key] = step

        rng = _repatriate(executor._get_rng(scope, program), self.mesh,
                          self._mesh_devs)
        with _tracing.step_span("spmd.step", cat="step", axis=self.axis):
            fetches, new_states, new_rng = step(scope, norm_feed, rng)
        for n, v in new_states.items():
            scope.set_var(n, v)
        scope.set_var(RNG_STATE_VAR, new_rng)
        level = _health.check_level()
        if level:
            # a NaN produced on ANY shard reaches the reduced/concatenated
            # fetch, so this one scan attributes shard divergence to the
            # fetched variable at site "spmd_fetch"
            _health_scan("spmd_fetch", zip(fetch_names, fetches), level)
        if _health.introspection_enabled():
            # multi-device runs are where buffer leaks hurt most — the
            # live-bytes gauge must not go dark on the SPMD-only path
            _record_live_device_memory()
        out = _finish_fetches(fetches, return_numpy, sync, site="spmd")
        wall = time.perf_counter() - t0
        _telemetry.record_spmd_step(self.axis, wall,
                                    step.collective_counts)
        # live-MFU sample: retained cost_analysis FLOPs of the SPMD
        # executable over this step's wall window, plus the step-time
        # breakdown (measured host-blocked delta; ring-allreduce
        # collective ESTIMATE from the mutable-state payload)
        n_dev = self.mesh.size
        dev_kind = mesh_device_kind(self.mesh)
        cost = step.dispatch.current_cost() or {}
        host = max(0.0, _telemetry.host_blocked_total() - host0)
        coll = _perfwatch.estimate_collective_seconds(
            dev_kind, n_dev, getattr(step, "payload_bytes", 0),
            sum(step.collective_counts.values()))
        _perfwatch.record_step(
            "spmd", wall, flops=cost.get("flops"),
            host_blocked=min(host, wall), collective_seconds=coll,
            device_kind=dev_kind, n_devices=n_dev)
        return out

    def _build(self, feed_names: Tuple[str, ...],
               fetch_names: Tuple[str, ...],
               policy: Optional["_precision.PrecisionPolicy"] = None):
        policy = policy if policy is not None \
            else _precision.resolve(self.program)
        desc = self.program.desc
        axis = self.axis
        n_dev = self.mesh.shape[axis]
        reads, writes = lowering.analyze_state_vars(desc, set(feed_names))
        persistable = {v.name for b in desc.blocks for v in b.vars.values()
                       if v.persistable}
        for n in fetch_names:
            if n in persistable and n not in reads and n not in writes:
                reads.append(n)
        const_reads = tuple(n for n in reads if n not in writes)
        mut_reads = tuple(n for n in reads if n in writes)
        writes = tuple(writes)
        is_test = self.program._is_test
        reduce = self.reduce

        # classify fetches statically by their inferred var shapes: scalar
        # fetches (loss-like) reduce across devices; batched fetches
        # concatenate shards (reference: FetchOpHandle merges per-device
        # results)
        def _is_scalar_fetch(n):
            vd = None
            for b in desc.blocks:
                if n in b.vars:
                    vd = b.vars[n]
                    break
            shp = vd.shape if vd is not None else None
            return shp is None or len(shp) == 0 or \
                (len(shp) == 1 and shp[0] == 1)

        scalar_fetch = {n: _is_scalar_fetch(n) for n in fetch_names}

        def device_step(feeds, const_states, mut_states, rng):
            env = dict(const_states)
            env.update(mut_states)
            env.update(feeds)
            if policy.cast_state:
                env = {k: _precision.cast_floating(v,
                                                   policy.compute_dtype)
                       for k, v in env.items()}
            # per-device rng stream (reference: different seed per trainer)
            rng_local = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            step_key, new_rng = jax.random.split(rng_local)
            with _precision.autocast(policy):
                lowering.lower_block(desc, 0, env, rng_key=step_key,
                                     is_test=is_test)
            fetches = []
            for n in fetch_names:
                if n not in env:
                    raise lowering.LoweringError(
                        f"fetch var '{n}' was not produced by the program")
                v = env[n]
                if scalar_fetch[n] and reduce == "mean":
                    v = jax.lax.pmean(v.astype(jnp.float32),
                                      axis).astype(v.dtype)
                fetches.append(v)
            new_states = {n: env[n] for n in writes if n in env}
            # advance the global rng identically on all devices
            new_global_rng = jax.random.split(rng)[1]
            return fetches, new_states, new_global_rng

        feed_specs = {n: P(axis) for n in feed_names}
        fetch_specs = [P() if scalar_fetch[n] else P(axis)
                       for n in fetch_names]
        sm = jax.shard_map(
            device_step,
            mesh=self.mesh,
            in_specs=(feed_specs,
                      {n: P() for n in const_reads},
                      {n: P() for n in mut_reads},
                      P()),
            out_specs=(fetch_specs,
                       {n: P() for n in writes},
                       P()),
            axis_names={axis},
            check_vma=False)
        jitted = _JitDispatch(jax.jit(sm), "spmd",
                              meta={"axis": axis, "devices": int(n_dev),
                                    "device_kind":
                                        mesh_device_kind(self.mesh)},
                              policy=policy.name)

        mesh = self.mesh  # pinned: resize() clears the cache, so a step
        # never outlives the mesh it was built for
        mesh_devs = self._mesh_devs

        def step(scope: Scope, feed, rng):
            def _state(n):
                v = scope.find_var(n)
                if v is None:
                    raise RuntimeError(
                        f"variable '{n}' missing from scope — run the "
                        f"startup program first")
                return _repatriate(v, mesh, mesh_devs)

            const_states = {n: _state(n) for n in const_reads}
            mut_states = {n: _state(n) for n in mut_reads}
            for n, v in feed.items():
                if v.shape and v.shape[0] % n_dev:
                    raise ValueError(
                        f"feed '{n}' batch {v.shape[0]} not divisible by "
                        f"{n_dev} devices on axis '{axis}'")
            # allreduce payload ≈ the mutable (gradient-updated) state:
            # what run()'s collective-time estimate is grounded on
            step.payload_bytes = sum(
                int(getattr(v, "nbytes", 0))
                for v in mut_states.values())
            return jitted(feed, const_states, mut_states, rng)

        # static per-program collective census: the c_* ops the transpiler
        # inserted, charged to the registry once per executed step
        counts: Dict[str, int] = {}
        for b in desc.blocks:
            for op in b.ops:
                if op.type.startswith("c_"):
                    counts[op.type] = counts.get(op.type, 0) + 1
        step.collective_counts = counts
        step.dispatch = jitted  # cost_analysis access for the MFU gauge
        step.payload_bytes = 0
        return step
