"""Scripted kill/resume cycles against the resilience stack.

Drives the fault-tolerant training loop (parallel.train.train_loop +
resilience.CheckpointManager) through whole-process crash + resume
cycles and reports SERVING-bench-style JSON lines: checkpoint save and
restore seconds, recovered-step overhead (steps re-executed because
they post-dated the last committed checkpoint), and whether every
resumed trajectory reproduced the uninterrupted baseline.

Each cycle: run the worker with PADDLE_TPU_FAULT_SPEC="step=K:crash"
(the injector os._exit()s the process at that exact step boundary —
a hard kill, not an exception), then relaunch the same command; the
worker restores via CheckpointManager.restore_latest() and finishes
the run. Losses are keyed by global step, so equivalence with the
baseline is a direct per-step comparison.

Run:  python tools/chaos_bench.py [--steps 24] [--save-every 4]
      [--kill-steps 7,15] [--smoke]

--smoke is the tier-1-safe mode the test suite invokes (CPU backend,
one short cycle) — it validates the whole kill/resume machinery and
the report schema, not absolute numbers.

Elastic mode (--elastic, RESILIENCE.md §Elasticity): instead of
kill-the-whole-process cycles, this drives a MEMBERSHIP chaos scenario
through the rendezvous store: a chief trainer plus world-1 member
processes rendezvous at world W; mid-training the orchestrator
SIGKILLs one member (its heartbeat goes stale → the chief re-forms on
W-1 survivors at the next checkpoint boundary, resharding the mesh-W
checkpoint onto mesh-(W-1) — NO process restarts), then spawns a
replacement (scale back out to W). The chief's loss trajectory must
match an uninterrupted fixed-world baseline within --tol, and the
report carries rendezvous seconds, resharding seconds, the generation
history, and the data-shard ledger check (no example lost or
double-seen across either membership change).

Run:  python tools/chaos_bench.py --elastic [--smoke]
      [--world 4] [--kill-at 2] [--join-at 8] [--tol 1e-3]

PS mode (--ps, RESILIENCE.md §Parameter-server fault tolerance): a CTR
workload (PS-sharded embedding + transpiled dense params, async mode)
trains against S pserver processes snapshotting through their own
CheckpointManager. Mid-run the orchestrator SIGKILLs one server and
respawns it on the same endpoint after --outage seconds; the respawn
restores its committed sparse+dense snapshot, and the single trainer
process rides the outage on the resilient client (reconnect + capped
backoff + idempotent retry + circuit breaker) with ZERO trainer
restarts. The report carries the loss-trajectory delta vs an
uninterrupted baseline (--tol), plus the degraded-seconds / rpc-retry /
reconnect metrics that prove the outage cost bounded step time (no
180 s socket stall).

Run:  python tools/chaos_bench.py --ps [--smoke]
      [--ps-servers 2] [--kill-at 4] [--outage 0.5] [--tol 0.05]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_args():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=24,
                    help="total training steps per run")
    ap.add_argument("--save-every", type=int, default=4)
    ap.add_argument("--kill-steps", type=str, default="7,15",
                    help="comma-separated steps to crash at, one cycle "
                    "per step")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--keep-last", type=int, default=2)
    ap.add_argument("--timeout-s", type=float, default=300.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU run for CI (overrides steps/kills)")
    # elastic membership chaos (see module docstring)
    ap.add_argument("--elastic", action="store_true",
                    help="membership chaos: kill/join members through "
                    "the rendezvous store instead of killing the "
                    "training process")
    ap.add_argument("--world", type=int, default=4,
                    help="elastic: starting world size")
    ap.add_argument("--kill-at", type=int, default=4,
                    help="elastic: SIGKILL one member once the chief "
                    "reports this step")
    ap.add_argument("--join-at", type=int, default=12,
                    help="elastic: spawn a replacement member once the "
                    "chief reports this step (after the scale-in)")
    ap.add_argument("--tol", type=float, default=1e-3,
                    help="elastic: relative per-step loss tolerance "
                    "vs the fixed-world baseline (cross-world float "
                    "reduction order differs)")
    ap.add_argument("--step-delay", type=float, default=0.15,
                    help="elastic: host-side seconds per step, so "
                    "membership changes land mid-run deterministically")
    # PS failover chaos (see module docstring)
    ap.add_argument("--ps", action="store_true",
                    help="parameter-server failover chaos: SIGKILL one "
                    "pserver mid-CTR-run, respawn it from its committed "
                    "snapshot, trainers ride through")
    ap.add_argument("--ps-servers", type=int, default=2,
                    help="ps: number of pserver processes")
    ap.add_argument("--outage", type=float, default=0.5,
                    help="ps: seconds between the SIGKILL and the "
                    "respawn")
    ap.add_argument("--rpc-deadline", type=float, default=60.0,
                    help="ps: trainer-side per-call retry budget "
                    "(PADDLE_TPU_PS_RPC_DEADLINE_S)")
    # internal PS roles
    ap.add_argument("--ps-server", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ps-trainer", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--endpoint", type=str, default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--snapshot-dir", type=str, default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--server-index", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--ps-endpoints", type=str, default="",
                    help=argparse.SUPPRESS)
    # internal: run one training process instead of orchestrating
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", type=str, default="",
                    help=argparse.SUPPRESS)
    # internal elastic roles
    ap.add_argument("--elastic-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--member", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--member-id", type=str, default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--wait-file", type=str, default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--rdzv-dir", type=str, default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--progress-file", type=str, default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--static-world", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--min-world", type=int, default=2,
                    help=argparse.SUPPRESS)
    return ap.parse_args()


# ---------------------------------------------------------------------------
# Worker mode: one training process (baseline, crashing, or resuming —
# the fault spec and the checkpoint dir contents decide which).
# ---------------------------------------------------------------------------


def run_worker(args) -> int:
    import jax
    import optax

    from paddle_tpu.models import lenet
    from paddle_tpu.observability import events
    from paddle_tpu.parallel import make_mesh, mesh_guard
    from paddle_tpu.parallel.train import (TrainStrategy, make_train_step,
                                           train_loop)
    from paddle_tpu.resilience import CheckpointManager
    from paddle_tpu.resilience.preemption import PREEMPT_EXIT_CODE

    params, axes = lenet.init(jax.random.key(0))
    mesh = make_mesh()
    data_key = jax.random.key(42)

    def batch_fn(step):
        if step >= args.steps:
            return None
        k = jax.random.fold_in(data_key, step)
        img = jax.random.normal(k, (args.batch, 1, 28, 28), "float32")
        label = jax.random.randint(jax.random.fold_in(k, 1),
                                   (args.batch, 1), 0, 10, "int32")
        return {"img": img, "label": label}

    with mesh_guard(mesh):
        init_state, step_fn = make_train_step(
            lenet.loss_fn, optax.adam(1e-3), mesh, axes,
            strategy=TrainStrategy(shard_optimizer_states=False))
        state = init_state(params)
        mgr = CheckpointManager(args.ckpt_dir,
                                keep_last_n=args.keep_last)
        resumed_from = None
        restored = mgr.restore_latest(state)
        if restored is not None:
            state = restored
            resumed_from = int(state.step)
        state, losses, stop = train_loop(
            step_fn, state, batch_fn, rng=jax.random.key(7),
            manager=mgr, save_every=args.save_every)

    save_s = [e["seconds"] for e in events.recent(n=None, kind="checkpoint")
              if e.get("site") == "manager_save" and "seconds" in e]
    restore_s = [e["seconds"] for e in events.recent(n=None, kind="restore")
                 if e.get("ok")]
    print(json.dumps({
        "worker": "chaos", "stop": stop, "final_step": int(state.step),
        "resumed_from": resumed_from,
        "losses": {str(k): float(v) for k, v in losses.items()},
        "save_seconds": save_s, "restore_seconds": restore_s,
    }), flush=True)
    return PREEMPT_EXIT_CODE if stop == "preempted" else 0


# ---------------------------------------------------------------------------
# Elastic roles
# ---------------------------------------------------------------------------

# heartbeat cadence shared by every elastic role: a member is declared
# dead after missing ~4 beats, fast enough that a kill lands within a
# couple of (step-delayed) training steps
_HB_S, _DEAD_S = 0.15, 0.6


def _example(i):
    """Global example `i` of the synthetic regression stream —
    derived from the INDEX alone, so every process (baseline, chief,
    any world size) sees the identical example for the same index."""
    import numpy as np

    rs = np.random.RandomState((1_000_003 * (int(i) + 1)) & 0x7FFFFFFF)
    return (rs.randn(8).astype(np.float32),
            rs.randn(4).astype(np.float32))


def _elastic_model():
    import jax
    import jax.numpy as jnp
    import optax

    from paddle_tpu.models.common import ParamStore, dense
    from paddle_tpu.parallel.train import make_train_step

    def make_params():
        # fresh arrays per call: init_state donates its params
        s = ParamStore(jax.random.key(0))
        s.dense("fc", 8, 4)
        return s.params

    store = ParamStore(jax.random.key(0))
    store.dense("fc", 8, 4)
    axes = store.axes

    def loss_fn(params, batch, rng):
        out = dense(params, "fc", batch["x"]).astype(jnp.float32)
        return jnp.mean((out - batch["y"]) ** 2)

    def build(mesh):
        return make_train_step(loss_fn, optax.adam(1e-2), mesh, axes)

    return build, make_params


def run_member(args) -> int:
    """A rendezvous member that holds a slot and heartbeats until
    killed — it models a slice host's liveness, nothing else (the
    single-host chief owns the actual devices). Deliberately does NOT
    import jax: members must be cheap to spawn and kill."""
    import time

    from paddle_tpu.distributed.rendezvous import FileRendezvous

    if args.wait_file:
        # pre-spawned joiner: interpreter+imports are already paid, so
        # the orchestrator can release the join with file latency, not
        # process-startup latency (keeps the smoke scenario's scale-out
        # inside its step budget)
        while not os.path.exists(args.wait_file):
            time.sleep(0.05)
    rdzv = FileRendezvous(args.rdzv_dir, args.member_id,
                          heartbeat_s=_HB_S, dead_after_s=_DEAD_S)
    rdzv.register()
    print(json.dumps({"member": args.member_id, "pid": os.getpid()}),
          flush=True)
    while True:  # until SIGKILLed by the orchestrator
        time.sleep(_HB_S)
        rdzv.register()
        # liveness stubs ack sealed generations so the chief's join
        # barrier completes (a real training member acks by
        # participating in rendezvous() itself)
        rdzv.ack_current()


def run_elastic_worker(args) -> int:
    """The chief trainer: elastic_train_loop over the rendezvous store,
    global batch split per step across live members by
    reader.ElasticShardPlan. Also the fixed-world baseline
    (--static-world N skips the store entirely)."""
    import time

    import jax
    import numpy as np

    from paddle_tpu.observability import events
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.mesh import MeshConfig, mesh_guard
    from paddle_tpu.parallel.train import train_loop
    from paddle_tpu.reader import ElasticShardPlan
    from paddle_tpu.resilience.atomic import json_dump

    build, make_params = _elastic_model()
    gb = args.batch
    plan = ElasticShardPlan(n_examples=args.steps * gb, global_batch=gb,
                            seed=5)
    consumed = []  # (step, world) ledger for the no-loss/no-dup check

    rdzv = None
    if not args.static_world:
        from paddle_tpu.distributed.rendezvous import FileRendezvous

        rdzv = FileRendezvous(args.rdzv_dir, "chief",
                              min_workers=args.min_world,
                              heartbeat_s=_HB_S, dead_after_s=_DEAD_S,
                              settle_s=0.3, timeout_s=60.0)

    def batch_fn(step):
        if step >= args.steps:
            return None
        if args.step_delay:
            time.sleep(args.step_delay)
        if rdzv is not None:
            info = rdzv.current()
            world = info.world_size if info is not None else 1
        else:
            world = args.static_world
        consumed.append((int(step), int(world)))
        if args.progress_file:
            json_dump({"step": int(step), "world": int(world)},
                      args.progress_file)
        # assemble the global batch the way the fleet would feed it:
        # each live member's plan slice, concatenated in rank order
        idx = np.concatenate([plan.worker_indices(step, r, world)
                              for r in range(world)])
        xs, ys = zip(*(_example(i) for i in idx))
        return {"x": np.stack(xs), "y": np.stack(ys)}

    if args.static_world:
        mesh = make_mesh(MeshConfig(dp=-1),
                         devices=jax.devices()[:args.static_world])
        with mesh_guard(mesh):
            init_state, step_fn = build(mesh)
            state, losses, stop = train_loop(
                step_fn, init_state(make_params()), batch_fn,
                rng=jax.random.key(7))
        history = []
    else:
        from paddle_tpu.distributed.elastic import elastic_train_loop
        from paddle_tpu.resilience import CheckpointManager

        mgr = CheckpointManager(args.ckpt_dir, keep_last_n=args.keep_last)
        state, losses, stop, history = elastic_train_loop(
            build, make_params, batch_fn, rdzv=rdzv, manager=mgr,
            save_every=args.save_every, rng=jax.random.key(7))

    # ledger check: with the worlds ACTUALLY used per step, the plan
    # must have assigned every consumed example exactly once
    ledger = []
    for step, world in consumed:
        if step in losses:  # executed steps only
            for r in range(world):
                ledger.extend(int(i) for i in
                              plan.worker_indices(step, r, world))
    expected = []
    for step in sorted(losses):
        expected.extend(int(i) for i in plan.batch_indices(step))
    plan_ok = sorted(ledger) == sorted(expected) and \
        len(set(ledger)) == len(ledger)

    rdzv_s = [e["seconds"] for e in events.recent(n=None, kind="rendezvous")
              if e.get("action") == "sealed" and "seconds" in e]
    reshard_s = [e["seconds"] for e in
                 events.recent(n=None, kind="restore_resharded")]
    lost = sorted({w for e in events.recent(n=None, kind="rendezvous")
                   for w in e.get("lost", [])})
    print(json.dumps({
        "worker": "elastic", "stop": stop, "pid": os.getpid(),
        "losses": {str(k): float(v) for k, v in losses.items()},
        "generations": [{"generation": h.generation,
                         "world": h.world_size} for h in history],
        "plan_ok": plan_ok,
        "rendezvous_seconds": rdzv_s, "resharding_seconds": reshard_s,
        "lost_members": lost,
    }), flush=True)
    return 0 if stop == "completed" else 1


def _read_progress(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def run_elastic_bench(args) -> int:
    """Orchestrate the elastic scenario: world W chief+members, kill one
    member mid-training (scale-in to W-1 at the next checkpoint
    boundary, no process restarts), spawn a replacement (scale-out back
    to W), and compare the chief's full loss trajectory against an
    uninterrupted fixed-world-W baseline."""
    import subprocess
    import time

    work = tempfile.mkdtemp(prefix="chaos_elastic_")
    rdzv_dir = os.path.join(work, "rdzv")
    progress = os.path.join(work, "progress.json")
    os.makedirs(rdzv_dir, exist_ok=True)
    failures = []
    members = {}

    def env_for(n_devices):
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.pop("PADDLE_TPU_FAULT_SPEC", None)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{n_devices}").strip()
        return env

    def spawn_member(mid, wait_file=""):
        cmd = [sys.executable, os.path.abspath(__file__), "--member",
               "--member-id", mid, "--rdzv-dir", rdzv_dir]
        if wait_file:
            cmd += ["--wait-file", wait_file]
        p = subprocess.Popen(
            cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=_REPO, env=dict(os.environ))
        members[mid] = p
        return p

    def wait_for(pred, timeout, what):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if pred():
                return True
            time.sleep(0.05)
        failures.append(f"timeout waiting for {what}")
        return False

    chief_cmd = [sys.executable, os.path.abspath(__file__),
                 "--elastic-worker", "--steps", str(args.steps),
                 "--save-every", str(args.save_every),
                 "--batch", str(args.batch),
                 "--keep-last", str(args.keep_last),
                 "--step-delay", str(args.step_delay),
                 "--min-world", str(args.min_world)]
    try:
        # -- baseline: uninterrupted fixed world W ------------------------
        base = subprocess.run(
            chief_cmd + ["--static-world", str(args.world)],
            capture_output=True, text=True, timeout=args.timeout_s,
            cwd=_REPO, env=env_for(args.world))
        base_rep = _elastic_report(base)
        if base.returncode != 0 or base_rep is None:
            print(base.stdout + base.stderr, file=sys.stderr)
            raise SystemExit("chaos_bench --elastic: baseline failed")
        base_losses = base_rep["losses"]

        # -- elastic run --------------------------------------------------
        members_dir = os.path.join(rdzv_dir, "members")

        def members_registered():
            return (os.path.isdir(members_dir)
                    and len(os.listdir(members_dir)) >= args.world - 1)

        join_gate = os.path.join(work, "join_gate")
        for i in range(1, args.world):
            spawn_member(f"m{i}")
        # the replacement is pre-spawned behind a file gate so the
        # scale-out lands with file latency, not interpreter startup
        spawn_member("m-replacement", wait_file=join_gate)
        if not wait_for(members_registered, 30, "members to register"):
            raise SystemExit("chaos_bench --elastic: members never joined")
        chief = subprocess.Popen(
            chief_cmd + ["--rdzv-dir", rdzv_dir, "--ckpt-dir",
                         os.path.join(work, "ckpt"),
                         "--progress-file", progress],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=_REPO, env=env_for(args.world))

        def chief_wait(pred, what):
            # a dead chief can never satisfy pred — fail fast with its
            # stderr instead of burning the whole timeout
            ok = wait_for(lambda: chief.poll() is not None or pred(),
                          args.timeout_s, what)
            if chief.poll() is not None and not pred():
                return False
            return ok

        victim = f"m{(args.world - 1) // 2 + 1}"
        alive = chief_wait(lambda: _read_progress(progress).get("step", -1)
                           >= args.kill_at, "kill step")
        if alive:
            members[victim].kill()
            chief_wait(lambda: _read_progress(progress).get("world")
                       == args.world - 1, "scale-in")
            chief_wait(lambda: _read_progress(progress).get("step", -1)
                       >= args.join_at, "join step")
            with open(join_gate, "w"):  # atomic-exempt: empty gate file, existence is the signal
                pass
            chief_wait(lambda: _read_progress(progress).get("world")
                       == args.world, "scale-out")

        try:
            out, err = chief.communicate(timeout=args.timeout_s)
        except subprocess.TimeoutExpired:
            chief.kill()
            out, err = chief.communicate()
            failures.append("chief timed out")
        rep = _elastic_report_text(out)
        if chief.returncode != 0 or rep is None:
            failures.append(f"chief rc={chief.returncode}: {err[-500:]}")
            rep = rep or {}
    finally:
        for p in members.values():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(work, ignore_errors=True)

    worlds = [g["world"] for g in rep.get("generations", [])]
    if rep:
        if rep.get("stop") != "completed":
            failures.append(f"chief stop={rep.get('stop')}")
        if not rep.get("plan_ok"):
            failures.append("data-shard ledger check failed: an example "
                            "was lost or double-seen across a resize")
        # the scenario itself: W -> W-1 (scale-in) -> W (scale-out)
        if args.world - 1 not in worlds:
            failures.append(f"never re-formed at world {args.world - 1}: "
                            f"{worlds}")
        elif args.world not in worlds[worlds.index(args.world - 1) + 1:]:
            failures.append(f"never scaled back out to {args.world}: "
                            f"{worlds}")
        if not rep.get("resharding_seconds"):
            failures.append("no restore_resharded event recorded")
        for step, loss in rep.get("losses", {}).items():
            ref = base_losses.get(step)
            if ref is None or abs(loss - ref) > \
                    args.tol * max(1.0, abs(ref)):
                failures.append(f"step {step}: elastic loss {loss} vs "
                                f"baseline {ref} beyond tol {args.tol}")
                break

    detail = {
        "steps": args.steps, "save_every": args.save_every,
        "world": args.world, "kill_at": args.kill_at,
        "join_at": args.join_at, "worlds": worlds,
        "generations": rep.get("generations", []),
        "lost_members": rep.get("lost_members", []),
        "plan_ok": rep.get("plan_ok"), "tol": args.tol,
        "failures": failures, "smoke": bool(args.smoke),
    }
    for metric, value, unit in (
            ("elastic_rendezvous_seconds_p50",
             _percentile(rep.get("rendezvous_seconds", []), 50), "s"),
            ("elastic_resharding_seconds_p50",
             _percentile(rep.get("resharding_seconds", []), 50), "s"),
            ("elastic_resize_count",
             max(0, len(worlds) - 1) if worlds else None, "resizes"),
            ("elastic_recovered_steps_mean", 0.0 if rep else None,
             "steps"),  # the chief never restarts in this scenario
            ("elastic_equivalence_ok", 0.0 if failures else 1.0, "bool")):
        print(json.dumps({
            "metric": metric,
            "value": round(value, 6) if isinstance(value, float) else value,
            "unit": unit, "detail": detail}), flush=True)
    if failures:
        print("\n".join(failures), file=sys.stderr)
    return 1 if failures else 0


def _elastic_report(proc):
    return _elastic_report_text(proc.stdout)


def _elastic_report_text(text):
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rep = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rep.get("worker") == "elastic":
                return rep
    return None


# ---------------------------------------------------------------------------
# PS failover roles (see module docstring: --ps)
# ---------------------------------------------------------------------------


def run_ps_server(args) -> int:
    """One pserver process (async mode, single trainer) with durable
    snapshots through its own CheckpointManager; serves until killed or
    shut down by the trainer. A respawn on the same endpoint +
    snapshot dir restores the committed tables at construction."""
    from paddle_tpu.ps.server import ParameterServer

    srv = ParameterServer(args.endpoint, num_trainers=1, mode="async",
                          snapshot_dir=args.snapshot_dir or None,
                          server_index=args.server_index)
    print(json.dumps({"ps_server": args.endpoint, "pid": os.getpid(),
                      "restored_vars": len(srv.vars),
                      "generation": srv._generation}), flush=True)
    srv.serve_forever()
    return 0


def run_ps_trainer(args) -> int:
    """The CTR trainer: PS-sharded embedding (distributed_lookup_table)
    + transpiled dense params, async mode, deterministic per-step
    batches. Snapshots every server each --save-every steps (the
    durable-state cadence), writes per-step progress for the
    orchestrator, and reports losses + the resilience metrics that
    prove a mid-run server kill cost bounded step time."""
    import time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.observability import metrics as _m
    from paddle_tpu.ops.distributed import bind_client
    from paddle_tpu.ps import (DistributeTranspiler,
                               DistributeTranspilerConfig, PSClient)
    from paddle_tpu.ps.sparse_table import init_sparse_table
    from paddle_tpu.resilience.atomic import json_dump

    eps = args.ps_endpoints.split(",")
    V, D = 40, 8
    rng = np.random.RandomState(0)
    table = rng.rand(V, D).astype("float32") * 0.1

    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 7
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        wf = pt.layers.data(name="wf", shape=[1], dtype="float32")
        label = pt.layers.data(name="label", shape=[1], dtype="float32")
        ids64 = pt.layers.cast(wf, "int64")
        emb = pt.layers.distributed_embedding(ids64, (V, D), "ctr_table",
                                              sparse_lr=0.3)
        emb = pt.layers.reshape(emb, shape=[-1, D])
        pred = pt.layers.fc(input=emb, size=1, act="sigmoid")
        loss = pt.layers.mean(pt.layers.log_loss(pred, label))
        pt.optimizer.SGD(0.05).minimize(loss)

    cfg = DistributeTranspilerConfig()
    cfg.sync_mode = False
    t = DistributeTranspiler(cfg)
    t.transpile(0, program=main, pservers=args.ps_endpoints, trainers=1,
                sync_mode=False)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    client = PSClient(eps, rpc_deadline_s=args.rpc_deadline)
    bind_client(client)
    t.publish_params(pt.global_scope(), client)
    init_sparse_table(client, "ctr_table", table)
    client.snapshot_servers()   # snapshot 0: the post-init state
    prog = t.get_trainer_program()

    def batch(step):
        rs = np.random.RandomState((step + 1) * 7919)
        ids = rs.randint(0, V, (16, 1))
        return {"wf": ids.astype(np.float32),
                "label": (ids % 3 == 0).astype(np.float32)}

    losses = {}
    step_secs = []
    snap_latest = -1
    for step in range(args.steps):
        if args.step_delay:
            time.sleep(args.step_delay)
        fd = batch(step)
        t0 = time.perf_counter()
        val = exe.run(prog, feed=fd, fetch_list=[loss])[0]
        step_secs.append(time.perf_counter() - t0)
        losses[step] = float(np.asarray(val).reshape(()))
        if args.save_every and (step + 1) % args.save_every == 0:
            client.snapshot_servers()
            snap_latest = step
        if args.progress_file:
            json_dump({"step": step, "snapshotted": snap_latest},
                      args.progress_file)

    snap = _m.snapshot()

    def total(name, outcome=None):
        out = 0.0
        for s in (snap.get(name) or {}).get("series", []):
            if outcome is None or \
                    s.get("labels", {}).get("outcome") == outcome:
                out += s.get("value", 0)
        return out

    print(json.dumps({
        "worker": "ps", "pid": os.getpid(),
        "losses": {str(k): v for k, v in losses.items()},
        "steps_done": len(losses),
        "max_step_s": round(max(step_secs), 4),
        "degraded_s": round(total("paddle_tpu_ps_degraded_seconds_total"),
                            4),
        "retries": int(total("paddle_tpu_ps_rpc_total", "retry")),
        "unavailable": int(total("paddle_tpu_ps_rpc_total",
                                 "unavailable")),
        "reconnects": int(total("paddle_tpu_ps_reconnects_total")),
    }), flush=True)
    client.shutdown_servers()
    return 0


def _ps_report(text):
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rep = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rep.get("worker") == "ps":
                return rep
    return None


def run_ps_bench(args) -> int:
    """Orchestrate the PS failover scenario: S servers + 1 CTR trainer;
    SIGKILL server 0 right after a committed snapshot, respawn it on
    the same endpoint after --outage seconds (it restores the
    snapshot), and require (a) the trainer rides through with ZERO
    restarts, (b) the full loss trajectory within --tol of an
    uninterrupted baseline, (c) the outage cost bounded step time,
    evidenced by the degraded-seconds / retry / reconnect metrics."""
    import socket as _socket
    import time

    work = tempfile.mkdtemp(prefix="chaos_ps_")
    failures = []
    procs = []

    def env_for():
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PADDLE_TPU_FAULT_SPEC", None)
        return env

    def free_eps(n):
        socks, eps = [], []
        for _ in range(n):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            eps.append(f"127.0.0.1:{s.getsockname()[1]}")
        for s in socks:
            s.close()
        return eps

    def spawn_server(i, ep, snap_dir, log):
        cmd = [sys.executable, os.path.abspath(__file__), "--ps-server",
               "--endpoint", ep, "--snapshot-dir", snap_dir,
               "--server-index", str(i)]
        p = subprocess.Popen(cmd, stdout=open(log, "a"),  # atomic-exempt: live log stream
                             stderr=subprocess.STDOUT, cwd=_REPO,
                             env=env_for())
        procs.append(p)
        return p

    def wait_ep(ep, timeout=20.0):
        host, port = ep.rsplit(":", 1)
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                _socket.create_connection((host, int(port)), 0.2).close()
                return True
            except OSError:
                time.sleep(0.05)
        return False

    def trainer_cmd(eps, progress=""):
        cmd = [sys.executable, os.path.abspath(__file__), "--ps-trainer",
               "--ps-endpoints", ",".join(eps),
               "--steps", str(args.steps),
               "--save-every", str(args.save_every),
               "--step-delay", str(args.step_delay),
               "--rpc-deadline", str(args.rpc_deadline)]
        if progress:
            cmd += ["--progress-file", progress]
        return cmd

    outage_s = None
    rep = {}
    try:
        # -- baseline: no faults ------------------------------------------
        base_eps = free_eps(args.ps_servers)
        for i, ep in enumerate(base_eps):
            spawn_server(i, ep, os.path.join(work, f"base_snap_{i}"),
                         os.path.join(work, f"base_server_{i}.log"))
        for ep in base_eps:
            if not wait_ep(ep):
                raise SystemExit(f"chaos --ps: baseline server {ep} "
                                 f"never bound")
        base = subprocess.run(trainer_cmd(base_eps), capture_output=True,
                              text=True, timeout=args.timeout_s,
                              cwd=_REPO, env=env_for())
        base_rep = _ps_report(base.stdout)
        if base.returncode != 0 or base_rep is None:
            print(base.stdout + base.stderr, file=sys.stderr)
            raise SystemExit("chaos --ps: baseline run failed")

        # -- chaos run ----------------------------------------------------
        eps = free_eps(args.ps_servers)
        servers = {}
        for i, ep in enumerate(eps):
            servers[i] = spawn_server(
                i, ep, os.path.join(work, f"snap_{i}"),
                os.path.join(work, f"server_{i}.log"))
        for ep in eps:
            if not wait_ep(ep):
                raise SystemExit(f"chaos --ps: server {ep} never bound")
        progress = os.path.join(work, "progress.json")
        trainer = subprocess.Popen(
            trainer_cmd(eps, progress), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, cwd=_REPO, env=env_for())

        def wait_progress(pred, what, timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                if trainer.poll() is not None:
                    return False  # dead trainer can't satisfy pred
                if pred(_read_progress(progress)):
                    return True
                time.sleep(0.05)
            failures.append(f"timeout waiting for {what}")
            return False

        # kill server 0 right AFTER a committed snapshot at/after
        # --kill-at: the restored state then trails the live state by at
        # most the couple of steps the kill latency admits (--tol
        # absorbs those lost updates)
        if wait_progress(lambda p: p.get("snapshotted", -1) >= args.kill_at,
                         "kill snapshot", args.timeout_s):
            victim = servers[0]
            victim.kill()
            victim.wait(timeout=10)
            t_kill = time.time()
            time.sleep(args.outage)
            servers[0] = spawn_server(
                0, eps[0], os.path.join(work, "snap_0"),
                os.path.join(work, "server_0.log"))
            if not wait_ep(eps[0]):
                failures.append("respawned server 0 never bound")
            outage_s = time.time() - t_kill
        try:
            out, err = trainer.communicate(timeout=args.timeout_s)
        except subprocess.TimeoutExpired:
            trainer.kill()
            out, err = trainer.communicate()
            failures.append("trainer timed out (outage not survived)")
        rep = _ps_report(out) or {}
        if trainer.returncode != 0 or not rep:
            failures.append(f"trainer rc={trainer.returncode}: "
                            f"{(err or '')[-500:]}")
        # -- acceptance ---------------------------------------------------
        if rep:
            if rep.get("steps_done") != args.steps:
                failures.append(f"trainer finished {rep.get('steps_done')}"
                                f"/{args.steps} steps")
            for step, loss in rep.get("losses", {}).items():
                ref = base_rep["losses"].get(step)
                if ref is None or abs(loss - ref) > \
                        args.tol * max(1.0, abs(ref)):
                    failures.append(
                        f"step {step}: chaos loss {loss} vs baseline "
                        f"{ref} beyond tol {args.tol}")
                    break
            if rep.get("reconnects", 0) < 1:
                failures.append("trainer never reconnected — did the "
                                "kill land?")
            if rep.get("retries", 0) < 1:
                failures.append("no rpc retries recorded during the "
                                "outage")
            if rep.get("degraded_s", 0.0) <= 0.0:
                failures.append("degraded-seconds metric stayed zero")
            # the no-180s-stall bound: the worst step costs at most the
            # outage plus breaker/backoff slack, never a socket timeout
            bound = (outage_s or args.outage) + 30.0
            if rep.get("max_step_s", 0.0) > bound:
                failures.append(f"max step {rep['max_step_s']}s exceeds "
                                f"outage+slack bound {bound:.1f}s")
        # respawned server restored its snapshot?
        try:
            with open(os.path.join(work, "server_0.log")) as f:
                boots = [json.loads(l) for l in f
                         if l.strip().startswith("{")]
            if len(boots) >= 2 and boots[-1].get("restored_vars", 0) < 1:
                failures.append("respawned server 0 restored no vars "
                                "(snapshot not found?)")
        except (OSError, ValueError) as e:
            failures.append(f"cannot verify respawn restore: {e}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        shutil.rmtree(work, ignore_errors=True)

    detail = {
        "steps": args.steps, "save_every": args.save_every,
        "servers": args.ps_servers, "kill_at": args.kill_at,
        "outage_requested_s": args.outage, "tol": args.tol,
        "trainer_restarts": 0,   # by construction: one trainer process
        "retries": rep.get("retries"), "reconnects": rep.get("reconnects"),
        "unavailable": rep.get("unavailable"),
        "failures": failures, "smoke": bool(args.smoke),
    }
    for metric, value, unit in (
            ("ps_outage_seconds",
             round(outage_s, 3) if outage_s else None, "s"),
            ("ps_degraded_seconds", rep.get("degraded_s"), "s"),
            ("ps_rpc_retries", rep.get("retries"), "count"),
            ("ps_reconnects", rep.get("reconnects"), "count"),
            ("ps_max_step_seconds", rep.get("max_step_s"), "s"),
            ("ps_equivalence_ok", 0.0 if failures else 1.0, "bool")):
        print(json.dumps({
            "metric": metric,
            "value": round(value, 6) if isinstance(value, float) else value,
            "unit": unit, "detail": detail}), flush=True)
    if failures:
        print("\n".join(failures), file=sys.stderr)
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# Orchestrator mode
# ---------------------------------------------------------------------------


def _spawn(args, ckpt_dir, fault_spec=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("PADDLE_TPU_FAULT_SPEC", None)
    if fault_spec:
        env["PADDLE_TPU_FAULT_SPEC"] = fault_spec
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--ckpt-dir", ckpt_dir, "--steps", str(args.steps),
           "--save-every", str(args.save_every),
           "--batch", str(args.batch), "--keep-last", str(args.keep_last)]
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=args.timeout_s, cwd=_REPO, env=env)


def _worker_report(proc):
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rep = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rep.get("worker") == "chaos":
                return rep
    return None


def _percentile(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1))))
    return xs[i]


def run_bench(args) -> int:
    from paddle_tpu.resilience.faults import CRASH_EXIT_CODE

    kill_steps = [int(s) for s in args.kill_steps.split(",") if s.strip()]
    work = tempfile.mkdtemp(prefix="chaos_bench_")
    failures = []
    save_s, restore_s, recovered = [], [], []

    base = _spawn(args, os.path.join(work, "baseline"))
    base_rep = _worker_report(base)
    if base.returncode != 0 or base_rep is None:
        print(base.stdout + base.stderr, file=sys.stderr)
        shutil.rmtree(work, ignore_errors=True)
        raise SystemExit("chaos_bench: baseline run failed")
    base_losses = base_rep["losses"]
    save_s += base_rep["save_seconds"]

    for kill in kill_steps:
        ckpt = os.path.join(work, f"kill_{kill}")
        crashed = _spawn(args, ckpt, fault_spec=f"step={kill}:crash")
        if crashed.returncode != CRASH_EXIT_CODE:
            failures.append(
                f"kill@{kill}: expected crash rc={CRASH_EXIT_CODE}, got "
                f"{crashed.returncode}: {crashed.stderr[-500:]}")
            continue
        resumed = _spawn(args, ckpt)
        rep = _worker_report(resumed)
        if resumed.returncode != 0 or rep is None:
            failures.append(f"kill@{kill}: resume failed rc="
                            f"{resumed.returncode}: {resumed.stderr[-500:]}")
            continue
        if rep["resumed_from"] is None:
            failures.append(f"kill@{kill}: resume found no checkpoint")
            continue
        recovered.append(kill - rep["resumed_from"])
        save_s += rep["save_seconds"]
        restore_s += rep["restore_seconds"]
        for step, loss in rep["losses"].items():
            ref = base_losses.get(step)
            if ref is None or abs(loss - ref) > 1e-5 * max(1.0, abs(ref)):
                failures.append(
                    f"kill@{kill}: step {step} loss {loss} != baseline "
                    f"{ref}")
                break
    shutil.rmtree(work, ignore_errors=True)

    detail = {
        "steps": args.steps, "save_every": args.save_every,
        "kill_steps": kill_steps, "cycles": len(kill_steps),
        "failures": failures, "smoke": bool(args.smoke),
    }
    for metric, value, unit in (
            ("chaos_save_seconds_p50", _percentile(save_s, 50), "s"),
            ("chaos_restore_seconds_p50", _percentile(restore_s, 50), "s"),
            ("chaos_recovered_steps_mean",
             round(sum(recovered) / len(recovered), 3) if recovered
             else None, "steps"),
            ("chaos_equivalence_ok", 0.0 if failures else 1.0, "bool")):
        print(json.dumps({
            "metric": metric,
            "value": round(value, 6) if isinstance(value, float) else value,
            "unit": unit, "detail": detail}), flush=True)
    if failures:
        print("\n".join(failures), file=sys.stderr)
    return 1 if failures else 0


def main() -> int:
    args = _build_args()
    sys.path.insert(0, _REPO)
    if args.ps_server:
        if not args.endpoint:
            raise SystemExit("--ps-server needs --endpoint")
        return run_ps_server(args)
    if args.ps_trainer:
        if not args.ps_endpoints:
            raise SystemExit("--ps-trainer needs --ps-endpoints")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return run_ps_trainer(args)
    if args.ps:
        # host-side CPU scenario end to end (pservers are host processes,
        # the trainer is forced to CPU): no TPU singleflight needed
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.smoke:
            args.steps, args.save_every = 12, 2
            args.kill_at, args.outage = 4, 0.5
            args.ps_servers = min(args.ps_servers, 2)
        if args.tol == 1e-3:
            # the elastic default is bit-tight; a PS kill legitimately
            # loses the couple of steps between the last snapshot and
            # the SIGKILL landing — 5% relative absorbs them
            args.tol = 0.05
        return run_ps_bench(args)
    if args.member:
        if not (args.member_id and args.rdzv_dir):
            raise SystemExit("--member needs --member-id and --rdzv-dir")
        return run_member(args)
    if args.elastic_worker:
        return run_elastic_worker(args)
    if args.worker:
        if not args.ckpt_dir:
            raise SystemExit("--worker needs --ckpt-dir")
        return run_worker(args)
    if args.elastic:
        if args.world < 3:
            raise SystemExit(
                "--elastic needs --world >= 3: the scenario kills one "
                "member and must keep world-1 at or above quorum")
        if args.min_world > args.world - 1:
            raise SystemExit(
                f"--min-world {args.min_world} would deadlock the "
                f"scale-in to {args.world - 1}")
        if args.smoke:
            # tier-1 safety: tiny CPU scenario, one kill + one join
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            args.steps, args.save_every = 18, 2
            args.kill_at, args.join_at = 2, 8
            args.world = min(args.world, 4)
        else:
            args.steps = max(args.steps, args.join_at + 8)
        if args.batch % args.world or args.batch % (args.world - 1):
            # global batch divisible by both worlds keeps the batch
            # dp-sharded through the scale-in, not silently replicated
            args.batch = args.world * (args.world - 1) \
                * max(1, args.batch // (args.world * (args.world - 1)))
        return run_elastic_bench(args)
    if args.smoke:
        # tier-1 safety: tiny, CPU-only, a single kill/resume cycle
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.steps, args.save_every = 8, 2
        args.kill_steps = "5"
    return run_bench(args)


if __name__ == "__main__":
    sys.exit(main())
