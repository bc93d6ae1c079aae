"""Sharded train-step builder — the ParallelExecutor of the rebuild.

Reference: ParallelExecutor clones the graph per device and inserts NCCL
all-reduces (parallel_executor.cc, multi_devices_graph_pass.cc:454). Here ONE
jit over a Mesh with NamedShardings on params/optimizer state/batch does the
same: GSPMD partitions the computation and inserts the collectives. The
BuildStrategy knobs map to:

  reduce_strategy AllReduce ↔ optimizer state replicated over 'dp'
  reduce_strategy Reduce    ↔ optimizer state sharded over 'dp' (ZeRO-1)
  gradient merge / batch-merge pass ↔ accum_steps (lax.scan of microbatches)
  recompute ↔ jax.checkpoint on the loss fn
  AMP ↔ bf16 activations in the model + fp32 params here
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import precision as _precision
from ..models.common import Params, ParamAxes, is_trainable
from ..observability import memwatch as _memwatch
from ..observability import tracing as _tracing
from .sharding import LogicalRules, current_rules, named_sharding_tree


@dataclasses.dataclass
class TrainStrategy:
    """The rebuild's BuildStrategy (details/build_strategy.h:37)."""

    shard_optimizer_states: bool = True   # Reduce/ZeRO-1 vs AllReduce
    accum_steps: int = 1                  # gradient merge (multi_batch_merge_pass)
    recompute: bool = False               # RecomputeOptimizer
    # Rematerialization policy when recompute=True (the reference's
    # RecomputeOptimizer(checkpoints=...) selects WHICH activations to
    # keep; here the jax.checkpoint policy does):
    #   None / "nothing"  - save nothing, recompute everything (blanket)
    #   "dots"            - save every matmul/einsum output (attention
    #                       scores and projections are NOT recomputed —
    #                       the long-sequence-friendly policy)
    #   "dots_no_batch"   - save contraction results with no batch dims
    #                       (weights-gradient reuse, smaller footprint)
    recompute_policy: Optional[str] = None
    clip_global_norm: Optional[float] = None


class TrainState:
    """params + opt state + step, all sharded.

    `loss_scale` is the dynamic loss-scaling state of a mixed-precision
    policy (core/precision.py init_loss_scale_state: scale, good_steps,
    cumulative overflow/growth counters) and None under f32/bf16 — a
    None subtree has no leaves, so checkpoints written before this
    field existed keep restoring unchanged, while mixed-precision
    checkpoints round-trip the scale bit-identically through
    CheckpointManager."""

    def __init__(self, params, opt_state, step, loss_scale=None):
        self.params = params
        self.opt_state = opt_state
        self.step = step
        self.loss_scale = loss_scale
        _live_states.add(self)

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step,
                self.loss_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)

# HBM owner attribution (memwatch): every live TrainState volunteers its
# param and optimizer trees. Provider callables (not a one-time array
# registration) because the donated update loop replaces every buffer
# each step; registered once at import — memwatch rebuilds the id→owner
# map per sweep, so tree_unflatten'd tracer instances that land in the
# WeakSet during jit tracing are harmless (their leaf ids never match a
# live device array).
_live_states: "weakref.WeakSet[TrainState]" = weakref.WeakSet()


def _live_param_arrays():
    for st in list(_live_states):
        yield from jax.tree_util.tree_leaves(st.params)


def _live_opt_arrays():
    for st in list(_live_states):
        yield from jax.tree_util.tree_leaves(st.opt_state)


_memwatch.register_provider("params", _live_param_arrays)
_memwatch.register_provider("optimizer", _live_opt_arrays)


def param_shardings(mesh: Mesh, axes: ParamAxes,
                    rules: Optional[LogicalRules] = None) -> Dict[str, NamedSharding]:
    rules = rules or current_rules()
    return {k: NamedSharding(mesh, rules.spec(v)) for k, v in axes.items()}


def opt_state_sharding_like(opt_state, pspec_of_param, mesh: Mesh,
                            shard_over_dp: bool):
    """Optimizer moments inherit their param's spec; scalars replicated.
    With shard_over_dp (ZeRO-1), moments additionally shard their first
    unsharded axis over 'dp'."""

    def one(leaf_path_spec):
        return leaf_path_spec

    def spec_for(leaf, pspec: P):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return NamedSharding(mesh, P())
        spec = list(pspec) + [None] * (leaf.ndim - len(pspec))
        if shard_over_dp:
            # shard the largest unsharded dim over dp if divisible
            for i, s in enumerate(spec):
                if s is None and leaf.shape[i] % mesh.shape["dp"] == 0 and \
                        leaf.shape[i] >= mesh.shape["dp"]:
                    spec[i] = "dp"
                    break
        return NamedSharding(mesh, P(*spec))

    return spec_for


def make_train_step(
    loss_fn: Callable[[Params, Dict[str, jax.Array], jax.Array], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_axes: ParamAxes,
    rules: Optional[LogicalRules] = None,
    strategy: Optional[TrainStrategy] = None,
    batch_spec: Optional[P] = None,
    has_aux: bool = False,
    precision=None,
):
    """Returns (init_state_fn, step_fn).

    loss_fn(params, batch, rng) -> scalar loss. step_fn(state, batch, rng)
    -> (state, loss), jitted over `mesh` with full shardings.

    `precision` selects the core/precision.py policy (name or
    PrecisionPolicy; default resolves PADDLE_TPU_PRECISION, else f32):

      f32         — today's step, bit for bit.
      bf16        — params/opt state initialized AND computed in bf16.
      mixed_bf16  — f32 master params + optimizer state, loss/grads
                    computed with bf16-cast params and batch, plus
                    DYNAMIC LOSS SCALING: the scale/good-step state
                    lives in TrainState.loss_scale (checkpointed by
                    CheckpointManager), nonfinite grads skip the
                    update and shrink the scale, growth_interval clean
                    steps grow it, and cumulative overflow/growth
                    counters feed paddle_tpu_amp_total via
                    sync_loss_scale_metrics (train_loop calls it).
    """
    strategy = strategy or TrainStrategy()
    rules = rules or current_rules()
    policy = _precision.resolve(explicit=precision)
    p_shardings = param_shardings(mesh, param_axes, rules)
    batch_spec = batch_spec if batch_spec is not None else rules.spec(("batch", "seq"))
    repl = NamedSharding(mesh, P())

    policies = {
        None: None,
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    if strategy.recompute_policy not in policies:
        raise ValueError(
            f"unknown recompute_policy {strategy.recompute_policy!r}; "
            f"choose from {sorted(k for k in policies if k)} or None")
    if strategy.recompute_policy is not None and not strategy.recompute:
        raise ValueError(
            "recompute_policy is set but recompute=False — enable "
            "recompute=True for the policy to take effect")
    if strategy.recompute:
        # policy=None is jax.checkpoint's own default (save nothing)
        loss_fn = jax.checkpoint(
            loss_fn, policy=policies[strategy.recompute_policy])

    tx = optimizer
    if strategy.clip_global_norm:
        clip = optax.clip_by_global_norm(strategy.clip_global_norm)

        def clip_update(updates, state, params=None):
            with jax.named_scope("clip"):
                return clip.update(updates, state, params)

        tx = optax.chain(
            optax.GradientTransformation(clip.init, clip_update), optimizer)

    def mask_fn(params):
        return {k: is_trainable(k) for k in params}

    tx = optax.masked(tx, mask_fn)

    def init_state(params: Params) -> TrainState:
        """Takes ownership of `params`: buffers may be aliased into the
        donated TrainState (the reference's overwrite-in-scope semantics,
        scope.h). Re-init or copy if the caller needs them afterwards."""
        # a boot span, kept whether or not a recording is on (the
        # benchmark's `setup_train_build_s` reads it); the step itself
        # gets no site: its first call's compile is a row of
        # `compile.requests` under its own name
        with _tracing.boot_span("boot.train_build"):
            return _init_state(params)

    def _init_state(params: Params) -> TrainState:
        if policy.cast_state:
            # pure low-precision: master weights themselves live at the
            # compute width (mixed policies keep f32 masters instead)
            params = {k: _precision.cast_floating(
                jnp.asarray(v), policy.compute_dtype)
                for k, v in params.items()}
        params = {
            k: jax.device_put(v, p_shardings[k]) for k, v in params.items()
        }
        opt_state = jax.jit(
            tx.init,
            out_shardings=_opt_shardings(tx, params, p_shardings))(params)
        step = jax.device_put(jnp.zeros((), jnp.int32), repl)
        loss_scale = _precision.init_loss_scale_state(policy)
        if loss_scale is not None:
            loss_scale = jax.device_put(loss_scale, repl)
        return TrainState(params, opt_state, step, loss_scale)

    def _opt_shardings(tx, params, p_shardings):
        shape = jax.eval_shape(tx.init, params)
        spec_for = opt_state_sharding_like(
            None, None, mesh, strategy.shard_optimizer_states)

        def leaf_sharding(path, leaf):
            # moments are dicts keyed like params → reuse param specs
            name = None
            for e in path:
                if hasattr(e, "key") and isinstance(getattr(e, "key"), str) \
                        and e.key in p_shardings:
                    name = e.key
            if name is not None:
                return spec_for(leaf, p_shardings[name].spec)
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map_with_path(leaf_sharding, shape)

    def microbatch_grads(fn, params, batch, rng):
        if strategy.accum_steps == 1:
            if has_aux:
                (loss, aux), grads = jax.value_and_grad(
                    fn, has_aux=True)(params, batch, rng)
                return loss, grads, aux
            loss, grads = jax.value_and_grad(fn)(params, batch, rng)
            return loss, grads, {}
        # gradient merge: scan over accum_steps microbatches
        # (reference: multi_batch_merge_pass.cc / gradient_merge)
        def mb(carry, xs):
            acc, loss_sum = carry
            mb_batch, mb_rng = xs
            if has_aux:
                (loss, aux), g = jax.value_and_grad(
                    fn, has_aux=True)(params, mb_batch, mb_rng)
            else:
                loss, g = jax.value_and_grad(fn)(params, mb_batch, mb_rng)
                aux = {}
            acc = jax.tree.map(jnp.add, acc, g)
            return (acc, loss_sum + loss), aux

        zero = jax.tree.map(jnp.zeros_like, params)
        n = strategy.accum_steps
        mb_batches = jax.tree.map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)
        rngs = jax.random.split(rng, n)
        (grads, loss_sum), auxs = jax.lax.scan(mb, (zero, 0.0), (mb_batches, rngs))
        # state updates (BN stats): keep the last microbatch's values
        aux = jax.tree.map(lambda a: a[-1], auxs) if has_aux else {}
        inv = 1.0 / n
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, grads), aux

    use_amp = policy.dynamic_loss_scale and policy.compute_dtype is not None

    def step_fn(state: TrainState, batch, rng):
        if policy.compute_dtype is not None:
            # compute-width batch: an already-bf16 input pipeline makes
            # this the identity; under jit the cast fuses either way
            batch = _precision.cast_tree(batch, policy.compute_dtype)
        if not use_amp:
            loss, grads, aux = microbatch_grads(loss_fn, state.params,
                                                batch, rng)
            # layer scope (HLO metadata): a profile's device time under
            # `optimizer` is the optax update and the parameter write
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = optax.apply_updates(state.params, updates)
            # aux = non-trainable state updates keyed like params (BN stats)
            for k, v in aux.items():
                params[k] = v.astype(params[k].dtype)
            return TrainState(params, opt_state, state.step + 1,
                              state.loss_scale), loss

        # mixed policy: bf16/f16 compute against f32 master params +
        # dynamic loss scaling (reference: contrib/mixed_precision
        # check_finite_and_unscale / update_loss_scaling ops, rebuilt
        # jnp-natively with the scale state inside TrainState)
        ls = state.loss_scale
        scale = ls["scale"]

        def scaled_loss(p, b, r):
            pc = _precision.cast_tree(p, policy.compute_dtype)
            if has_aux:
                loss, aux = loss_fn(pc, b, r)
                return loss.astype(jnp.float32) * scale, aux
            return loss_fn(pc, b, r).astype(jnp.float32) * scale

        loss_s, grads_s, aux = microbatch_grads(scaled_loss, state.params,
                                                batch, rng)
        inv = 1.0 / scale
        # grads come back f32 (the param cast's transpose casts up);
        # astype guards exotic loss_fns that detach to compute dtype
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv,
                             grads_s)
        loss = loss_s * inv
        finite = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            finite = finite & jnp.all(jnp.isfinite(g))

        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        for k, v in aux.items():
            new_params[k] = v.astype(new_params[k].dtype)
        # overflow skips the whole update: params AND optimizer state
        # keep their pre-step values (select, so the nonfinite updates
        # never propagate)
        params = jax.tree.map(lambda new, old: jnp.where(finite, new, old),
                              new_params, state.params)
        opt_state = jax.tree.map(
            lambda new, old: jnp.where(finite, new, old),
            new_opt, state.opt_state)

        good = ls["good_steps"] + 1
        grow = finite & (good >= policy.growth_interval)
        new_scale = jnp.where(
            finite,
            jnp.where(grow,
                      jnp.minimum(scale * policy.incr_ratio,
                                  policy.max_loss_scale),
                      scale),
            jnp.maximum(scale * policy.decr_ratio,
                        policy.min_loss_scale))
        new_ls = {
            "scale": new_scale.astype(jnp.float32),
            "good_steps": jnp.where(finite & ~grow, good,
                                    0).astype(jnp.int32),
            "overflows": ls["overflows"] + (~finite).astype(jnp.int32),
            "growths": ls["growths"] + grow.astype(jnp.int32),
        }
        return TrainState(params, opt_state, state.step + 1, new_ls), loss

    state_shardings_cache = {}

    def _jit_for(state: TrainState, batch):
        key = id(mesh)
        if key not in state_shardings_cache:
            st_sh = TrainState(
                p_shardings,
                jax.tree.map(lambda x: x.sharding, state.opt_state),
                repl,
                jax.tree.map(lambda x: repl, state.loss_scale))
            def leaf_sharding(x):
                spec = []
                for i, ax in enumerate(tuple(batch_spec)[:x.ndim]):
                    if isinstance(ax, str) and x.shape[i] % mesh.shape[ax] == 0:
                        spec.append(ax)
                    else:
                        spec.append(None)  # indivisible dim stays replicated
                return NamedSharding(mesh, P(*spec))

            batch_shardings = jax.tree.map(leaf_sharding, batch)
            state_shardings_cache[key] = jax.jit(
                step_fn,
                in_shardings=(st_sh, batch_shardings, repl),
                out_shardings=(st_sh, repl),
                donate_argnums=(0,),
            )
        return state_shardings_cache[key]

    def jitted_step(state: TrainState, batch, rng):
        return _jit_for(state, batch)(state, batch, rng)

    # the step's jax.stages.Lowered for the same arguments (arrays or
    # ShapeDtypeStructs): what compiles can be read — kernels present,
    # memory_analysis() — without running a step
    jitted_step.lower = lambda state, batch, rng: _jit_for(
        state, batch).lower(state, batch, rng)

    return init_state, jitted_step


def sync_loss_scale_metrics(state: TrainState,
                            last: Optional[Dict[str, Any]] = None
                            ) -> Optional[Dict[str, Any]]:
    """Diff TrainState.loss_scale's cumulative device counters against
    `last` (the previous return value) and tick
    paddle_tpu_amp_total{event=overflow|growth|skip} + the loss-scale
    gauge; overflows also land as `amp_overflow` events. Reads three
    device scalars, so callers sync at a cadence they already block at
    (train_loop: per step in sync mode, at drain in async mode).
    Returns the new cumulative snapshot (None loss_scale → `last`
    unchanged). `last=None` BASELINES without recording — a restored
    checkpoint's lifetime counters must not replay as fresh events."""
    from ..observability import telemetry as _telemetry

    ls = getattr(state, "loss_scale", None)
    if ls is None:
        return last
    cur = {"overflows": int(ls["overflows"]),
           "growths": int(ls["growths"]),
           "scale": float(ls["scale"])}
    _telemetry.AMP_LOSS_SCALE.set(cur["scale"])
    if last is None:
        return cur
    prev = last
    step = None
    try:
        step = int(state.step)
    except Exception:  # lint-exempt:swallow: step is optional telemetry on a diffed counter
        pass
    d_over = cur["overflows"] - int(prev.get("overflows", 0))
    d_grow = cur["growths"] - int(prev.get("growths", 0))
    _telemetry.record_amp("overflow", d_over, step=step,
                          scale=cur["scale"])
    _telemetry.record_amp("skip", d_over)
    _telemetry.record_amp("growth", d_grow, scale=cur["scale"])
    return cur


def train_loop(step_fn, state: TrainState, batches, *, rng=None,
               manager=None, save_every: Optional[int] = None,
               controller=None, max_steps: Optional[int] = None,
               fetch_window: Optional[int] = None,
               resize_check: Optional[Callable[[], bool]] = None):
    """Fault-tolerance-aware driver for a `make_train_step` step_fn.

    The step boundary is the only safe interruption point (no donated
    buffers in flight, device state consistent), so everything the
    resilience layer does hangs off this loop:

      - fault injection: `faults.check("step", step=N)` fires before
        each step — `PADDLE_TPU_FAULT_SPEC="step=N:crash"` kills the
        process exactly there, which is how the kill-and-resume tests
        provoke arbitrary-step deaths;
      - preemption: when a graceful stop was requested (SIGTERM with
        PADDLE_TPU_PREEMPT_SIGNALS set, or programmatically), the loop
        writes a final checkpoint via `manager` and returns
        stop="preempted" — the caller exits with PREEMPT_EXIT_CODE;
      - periodic checkpoints: every `save_every` completed steps,
        `manager.save(state)` (commit marker + retention inside);
      - recovery: a NumericsError from the post-step loss check (or a
        blown warn-anomaly budget) is routed to `controller.handle`,
        which skips the batch, rolls the state back to the last
        committed checkpoint, or aborts per its RecoveryPolicy.

    `batches` is either an iterable of batches or a callable
    `batch_fn(step) -> batch | None` (None stops the loop). The callable
    form keys data on the GLOBAL step number, which is what makes a
    resumed run replay the exact uninterrupted trajectory — and what a
    rollback needs to re-feed the steps it rewound over (an iterator
    cannot rewind; with one, a rollback continues on fresh batches).
    Per-step randomness is `jax.random.fold_in(rng, step)` for the same
    reason. Returns (state, losses, stop) where `losses` maps executed
    step number -> float loss and `stop` is
    "completed" | "preempted" | "exhausted" | "resize".

    `resize_check` is the elastic-membership hook
    (distributed.elastic): it is consulted immediately AFTER each
    periodic checkpoint commits — the only boundary where every
    surviving worker has identical durable state — and a True return
    stops the loop with stop="resize" so the driver can re-rendezvous,
    re-form the mesh for the new world size, and reshard the
    just-committed checkpoint onto it. It requires `manager` +
    `save_every`; without periodic checkpoints there is no safe
    boundary to re-form at.

    Loss fetching is ASYNC by default: `float(loss)` every step is a
    full host round trip that serializes the device on the host loop,
    so losses are parked as lazy FetchHandles and resolved only when
    `fetch_window` (default 2) of them are outstanding — the host runs
    ahead dispatching while the device computes, blocking only when it
    outruns the device by the window (recorded as host-blocked time).
    The trajectory is bit-identical to synchronous fetching: the same
    arrays are resolved, just later. A per-step loss CONSUMER forces
    fetch_window=1 automatically: health numerics checks and recovery
    controllers must see step N's loss before step N+1 dispatches.
    """
    import time as _time

    from collections import deque as _deque

    from ..core import async_exec as _async
    from ..observability import events as _events
    from ..observability import health as _health
    from ..ps import errors as _ps_errors
    from ..resilience import faults as _faults
    from ..resilience import preemption as _preempt

    _preempt.maybe_install_from_env()
    if resize_check is not None and (manager is None or not save_every):
        raise ValueError(
            "resize_check requires manager + save_every — without "
            "periodic checkpoints there is no boundary at which it is "
            "ever consulted")
    if controller is not None:
        controller.attach()
    if rng is None:
        rng = jax.random.key(0)
    get_batch = batches if callable(batches) else None
    batch_iter = iter(batches) if get_batch is None else None
    losses: Dict[int, float] = {}
    steps_done = 0
    stop = "completed"
    window = max(1, int(fetch_window or _async.DEFAULT_IN_FLIGHT))
    if controller is not None or _health.check_level():
        window = 1  # per-step loss consumers need the value NOW
    pending: "_deque[Tuple[int, Any]]" = _deque()

    def _resolve_oldest():
        step_i, h = pending.popleft()
        # backpressure keeping run-ahead bounded, not a pipeline stall
        losses[step_i] = float(np.asarray(
            h.result(stall=False)[0]).reshape(()))

    # async mode tracks the step number host-side: `int(state.step)` is
    # a device fetch of the step JUST dispatched, so deriving it every
    # iteration would re-serialize the loop the fetch window exists to
    # overlap. The counter is seeded from the (possibly restored) state
    # once and advances with each successful step — the sync/controller
    # paths keep reading the authoritative device value (rollback
    # rewinds it).
    host_step = int(state.step) if window > 1 else None
    amp_seen = sync_loss_scale_metrics(state) \
        if getattr(state, "loss_scale", None) is not None else None
    t0 = _time.perf_counter()
    try:
        while True:
            if max_steps is not None and steps_done >= max_steps:
                stop = "exhausted"
                break
            step_no = host_step if host_step is not None \
                else int(state.step)
            _faults.check("step", step=step_no)
            if _preempt.stop_requested():
                stop = "preempted"
                if manager is not None and not manager.is_committed(
                        manager.step_dir(step_no)):
                    manager.save(state)
                break
            if controller is not None and controller.should_act():
                action, state = controller.handle(None, state,
                                                  step=step_no)
                if action == "rollback":
                    continue  # step_no re-derives from the rewound state
            if get_batch is not None:
                batch = get_batch(step_no)
                if batch is None:
                    break
            else:
                batch = next(batch_iter, None)
                if batch is None:
                    break
            step_rng = jax.random.fold_in(rng, step_no)
            try:
                state, loss = step_fn(state, batch, step_rng)
                if window > 1:
                    # resolve-first: never more than `window` handles
                    # (and their device buffers) outstanding at once
                    while len(pending) >= window:
                        _resolve_oldest()
                    pending.append((step_no, _async.FetchHandle(
                        [loss], site="train_loop")))
                    host_step += 1
                else:
                    loss_val = float(loss)
                    if _health.check_level():
                        _health.check_numerics(
                            "trainer_loss", [("loss", loss_val)],
                            step=step_no)
                    losses[step_no] = loss_val
                    if amp_seen is not None:
                        # sync mode already blocked on the loss; the
                        # loss-scale counters ride the same sync so
                        # overflow events carry exact step attribution
                        amp_seen = sync_loss_scale_metrics(state,
                                                           amp_seen)
            except (_health.NumericsError, _ps_errors.PSUnavailableError) \
                    as e:
                # PSUnavailableError: a PS pull/push exhausted its
                # reconnect+retry budget mid-step (the resilient client
                # already rode out anything shorter). Routed through the
                # same RecoveryPolicy as a numerics anomaly: skip_batch
                # retries against the (possibly respawned) server next
                # step, rollback rewinds past any half-applied pushes,
                # abort propagates.
                if controller is None:
                    raise
                action, state = controller.handle(e, state, step=step_no)
                if action == "skip_batch":
                    steps_done += 1
                continue
            steps_done += 1
            completed = host_step if host_step is not None \
                else int(state.step)
            if (manager is not None and save_every
                    and completed % save_every == 0):
                manager.save(state)
                if resize_check is not None and resize_check():
                    # elastic membership changed: the checkpoint just
                    # committed IS the re-rendezvous boundary — hand
                    # control back so the driver can re-form the mesh
                    # and reshard (distributed.elastic)
                    stop = "resize"
                    break
    finally:
        while pending:  # drain: every executed step's loss lands
            _resolve_oldest()
        if amp_seen is not None:
            # async mode: aggregate outcome counts land at drain time
            amp_seen = sync_loss_scale_metrics(state, amp_seen)
        if controller is not None:
            controller.detach()
    seconds = _time.perf_counter() - t0
    _events.emit("step_summary", site="train_loop", steps=steps_done,
                 stop=stop, final_step=int(state.step),
                 seconds=round(seconds, 6))
    return state, losses, stop
