"""Parameter server.

Reference: operators/distributed_ops/listen_and_serv_op.cc — the pserver
event loop. Sync mode (:110): wait for send-barrier from all trainers, run
the optimize blocks on the accumulated gradients, release the get-barrier.
Async mode (:226): apply the optimize block per arriving gradient. GEO mode
(communicator.h:323): trainers push parameter deltas that are summed in.

The optimize logic reuses the framework's own op kernels (the reference
runs the very optimize sub-blocks the transpiler moved over) — the
transpiler ships each param's optimize OpDescs; the server executes them
eagerly on CPU via the shared registry. A HeartBeatMonitor
(heart_beat_monitor.h:54) tracks per-trainer liveness.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import socketserver
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

from ..observability import events as _events
from ..observability import metrics as _m
from ..observability import tracing as _tracing
from ..resilience import faults as _faults
from .protocol import (CID_FIELD, SEQ_FIELD, TRACE_FIELD, recv_msg,
                       send_msg)

_log = logging.getLogger("paddle_tpu.ps")

DEDUP_REPLIES = _m.counter(
    "paddle_tpu_ps_dedup_replies_total",
    "Retried requests answered from the reply cache instead of "
    "re-applying (idempotent-retry envelope)", labelnames=("op",))


class HeartBeatMonitor:
    """reference: operators/distributed/heart_beat_monitor.h:54 — worker
    states UNINITED/RUNNING/COMPLETED; a thread logs workers that stop
    beating."""

    UNINITED, RUNNING, COMPLETED = 0, 1, 2

    def __init__(self, num_trainers: int, timeout_s: float = 60.0):
        self.states = {i: self.UNINITED for i in range(num_trainers)}
        self.last_beat = {i: 0.0 for i in range(num_trainers)}
        self.timeout_s = timeout_s
        self.lost: List[int] = []
        # deferred import: the analysis package must not load during
        # package bootstrap; constructors only run after it
        from ..analysis import lockcheck as _lockcheck

        self._lock = _lockcheck.Lock(
            "ps.server.HeartBeatMonitor._lock")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def beat(self, trainer_id: int, state: Optional[int] = None):
        with self._lock:
            self.last_beat[trainer_id] = time.time()
            self.states[trainer_id] = (self.RUNNING if state is None
                                       else state)

    def _watch(self):
        while not self._stop.wait(self.timeout_s / 4):
            now = time.time()
            with self._lock:
                for tid, st in self.states.items():
                    if st == self.RUNNING and \
                            now - self.last_beat[tid] > self.timeout_s and \
                            tid not in self.lost:
                        self.lost.append(tid)
                        print(f"[ps] LostWorkerMonitor: trainer {tid} "
                              f"missed heartbeats for {self.timeout_s}s")

    def stop(self):
        self._stop.set()
        # the watcher wakes from its Event.wait on set(); join so stop()
        # returning means the thread is actually gone (stopjoin pass)
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)


def snapshot_config_from_env(endpoint: str) -> Dict[str, Any]:
    """ParameterServer durability kwargs from the launcher env contract:

      PADDLE_TPU_PS_SNAPSHOT_DIR      root; each server snapshots into
                                      <root>/server_<index> (or a
                                      sanitized endpoint when no index
                                      is exported)
      PADDLE_TPU_PS_SERVER_INDEX      this server's slot number (also
                                      the `ps_server=N` fault-site id)
      PADDLE_TPU_PS_SNAPSHOT_EVERY_S  periodic-snapshot cadence
                                      (unset/0: on-demand `snapshot`
                                      RPCs only)

    Empty dict when PADDLE_TPU_PS_SNAPSHOT_DIR is unset — a server
    without the env runs exactly as before (no durability)."""
    root = os.environ.get("PADDLE_TPU_PS_SNAPSHOT_DIR")
    if not root:
        return {}
    idx = os.environ.get("PADDLE_TPU_PS_SERVER_INDEX")
    sub = (f"server_{int(idx)}" if idx not in (None, "")
           else endpoint.replace(":", "_").replace("/", "_"))
    every = os.environ.get("PADDLE_TPU_PS_SNAPSHOT_EVERY_S")
    out: Dict[str, Any] = {"snapshot_dir": os.path.join(root, sub)}
    if every:
        try:
            out["snapshot_every_s"] = float(every) or None
        except ValueError:
            pass  # lint-exempt:swallow: malformed cadence env falls back to on-demand snapshots
    if idx not in (None, ""):
        out["server_index"] = int(idx)
    return out


def _np_to_py(o):
    """json default= hook: numpy scalars in shipped opt-desc attrs."""
    if hasattr(o, "item"):
        return o.item()
    raise TypeError(f"not JSON-serializable: {type(o).__name__}")


def _snapshot_save(path: str, state: dict) -> None:
    """CheckpointManager save_fn: the server's whole state as atomic
    npz payloads (dense values + sparse shards in vars.npz, optimizer
    accumulators in aux.npz) plus a JSON meta (opt descs, grad names,
    aux ownership, sync generation, snapshot counter). The manager's
    commit marker is written only after all three land."""
    from ..resilience import atomic as _atomic

    os.makedirs(path, exist_ok=True)
    _atomic.np_savez(os.path.join(path, "vars.npz"), **state["values"])
    _atomic.np_savez(os.path.join(path, "aux.npz"), **state["aux"])
    _atomic.json_dump(state["meta"], os.path.join(path, "meta.json"),
                      default=_np_to_py)


def _snapshot_restore(path: str, template) -> dict:
    """CheckpointManager restore_fn: inverse of _snapshot_save.
    `template` is unused (the server repopulates its own dicts)."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    with np.load(os.path.join(path, "vars.npz"), allow_pickle=False) as z:
        values = {k: z[k] for k in z.files}
    with np.load(os.path.join(path, "aux.npz"), allow_pickle=False) as z:
        aux = {k: z[k] for k in z.files}
    return {"values": values, "aux": aux, "meta": meta}


class _VarState:
    __slots__ = ("value", "recv", "opt_descs", "grad_name", "lock")

    def __init__(self, value, opt_descs, grad_name=None):
        self.value = value
        # sync mode: per-trainer received grads for the CURRENT step,
        # keyed by trainer_id. Replace-on-resend semantics (a trainer
        # that dies and rejoins mid-step must not double-count) — the
        # reference's per-var received state, listen_and_serv_op.cc:178
        # ResetReceivedVars.
        self.recv: Dict[int, np.ndarray] = {}
        self.opt_descs = opt_descs  # [OpDesc dicts] from the transpiler
        # actual grad var name the descs reference (clipping and other
        # grad-rewriting passes rename it away from <param>@GRAD)
        self.grad_name = grad_name or None
        from ..analysis import lockcheck as _lockcheck  # deferred

        self.lock = _lockcheck.Lock("ps.server._VarState.lock")


class ParameterServer:
    """One endpoint's server. mode: 'sync' | 'async' | 'geo'.

    Durability (RESILIENCE.md §Parameter-server fault tolerance): with
    `snapshot_dir` set, the server owns a resilience.CheckpointManager
    over its whole state — dense var values, sparse-table shards,
    optimizer aux, opt descs and the sync generation — and (a) restores
    the newest committed snapshot at construction, so a respawned
    server RESUMES its tables instead of reinitializing, (b) snapshots
    periodically every `snapshot_every_s` seconds when state changed,
    and (c) snapshots on demand via the `snapshot` RPC (the trainer's
    checkpoint cadence). Commit markers, retention and corrupt-fallback
    come from the manager; payloads are atomic npz/json writes.

    Retried-request dedupe: requests carrying the (cid, seq) envelope
    (ps/protocol.py) are answered from a bounded last-reply-per-cid
    cache when the seq repeats — a resent push/barrier whose reply was
    lost on the wire is never applied twice within one server
    incarnation."""

    _REPLY_CACHE_CIDS = 512
    _MUTATING_OPS = frozenset((
        "init_var", "init_aux", "init_aux_many", "send_grad",
        "send_grads", "send_delta", "send_barrier", "push_sparse_grad",
        "rejoin"))

    def __init__(self, endpoint: str, num_trainers: int, mode: str = "sync",
                 dc_asgd_lambda: float = 0.0,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every_s: Optional[float] = None,
                 snapshot_keep_last: int = 3,
                 server_index: int = 0):
        self.host, port = endpoint.rsplit(":", 1)
        self.port = int(port)
        self.num_trainers = num_trainers
        self.mode = mode
        self.server_index = int(server_index)
        # DC-ASGD (reference: distribute_transpiler.py:2050
        # _append_dc_asgd_ops): async staleness compensation
        # g' = g + λ·g⊙g⊙(w_now - w_at_pull); per-trainer pull snapshots
        self.dc_lambda = float(dc_asgd_lambda)
        self._pull_snapshots: Dict[tuple, np.ndarray] = {}
        self.vars: Dict[str, _VarState] = {}
        self.aux: Dict[str, np.ndarray] = {}   # optimizer accumulators
        self.aux_owner: Dict[str, str] = {}    # aux name -> owning param
        self.monitor = HeartBeatMonitor(num_trainers)
        from ..analysis import lockcheck as _lockcheck  # deferred

        self._barrier_lock = _lockcheck.Lock(
            "ps.server.ParameterServer._barrier_lock")
        self._send_barrier: set = set()
        self._step_done = _lockcheck.Condition(
            self._barrier_lock,
            name="ps.server.ParameterServer._step_done")
        self._generation = 0
        # global-shuffle exchange plane (reference:
        # DatasetImpl::GlobalShuffle, data_set.cc:295 — records re-routed
        # across trainers through the fleet RPC; here the PS coordinates
        # the pass seed, buffers per-target record batches, and barriers
        # until every trainer has routed before handing shards back)
        self._shuf_lock = _lockcheck.Lock(
            "ps.server.ParameterServer._shuf_lock")
        self._shuf_cv = _lockcheck.Condition(
            self._shuf_lock, name="ps.server.ParameterServer._shuf_cv")
        self._shuf_pass = 0
        self._shuf_seed = 0
        self._shuf_begun: set = set()
        self._shuf_done: set = set()
        self._shuf_taken: set = set()
        self._shuf_buf: Dict[int, list] = {}
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        # retried-request dedupe: cid -> (seq, reply), bounded LRU
        self._reply_cache: "OrderedDict[str, tuple]" = OrderedDict()
        self._reply_lock = _lockcheck.Lock(
            "ps.server.ParameterServer._reply_lock")
        # durable snapshots
        self._snap_mgr = None
        self._snap_lock = _lockcheck.Lock(
            "ps.server.ParameterServer._snap_lock")
        self._snap_step = 0
        self._dirty = threading.Event()
        self._snap_stop = threading.Event()
        self._snap_thread: Optional[threading.Thread] = None
        if snapshot_dir:
            from ..resilience.checkpoint_manager import CheckpointManager

            self._snap_mgr = CheckpointManager(
                snapshot_dir, keep_last_n=max(1, int(snapshot_keep_last)),
                save_fn=_snapshot_save, restore_fn=_snapshot_restore)
            self._restore_from_snapshot()
            if snapshot_every_s:
                self._snap_thread = threading.Thread(
                    target=self._snapshot_loop, args=(float(snapshot_every_s),),
                    daemon=True)
                self._snap_thread.start()

    # -- durable snapshots (resilience.CheckpointManager) -------------------

    def _collect_state(self) -> dict:
        """Copy-out of everything a respawn needs. Values are copied
        under each var's lock (per-var consistent; in sync mode a
        snapshot between barriers is globally consistent, in async mode
        per-var is the strongest consistency the mode itself offers)."""
        values: Dict[str, np.ndarray] = {}
        var_meta: Dict[str, dict] = {}
        for name, vs in list(self.vars.items()):
            # lock-id: ps.server._VarState.lock
            with vs.lock:
                values[name] = np.array(vs.value, copy=True)
            var_meta[name] = {"opt_descs": vs.opt_descs,
                              "grad_name": vs.grad_name}
        aux = {n: np.array(v, copy=True)
               for n, v in list(self.aux.items())}
        with self._barrier_lock:
            generation = self._generation
        return {"values": values, "aux": aux,
                "meta": {"vars": var_meta,
                         "aux_owner": dict(self.aux_owner),
                         "generation": int(generation),
                         "snap_step": int(self._snap_step),
                         "mode": self.mode,
                         "server_index": self.server_index}}

    def snapshot(self) -> Optional[str]:
        """Write one committed snapshot now (no-op without a snapshot
        dir). Serialized so the periodic thread and the `snapshot` RPC
        can't interleave step numbers."""
        if self._snap_mgr is None:
            return None
        with self._snap_lock:
            self._dirty.clear()     # mutations during collect re-set it
            state = self._collect_state()
            d = self._snap_mgr.save(state, step=self._snap_step)
            self._snap_step += 1
            return d

    def _restore_from_snapshot(self):
        """Boot-time resume: repopulate vars/aux/generation from the
        newest committed snapshot. Corrupt snapshots fall back to older
        ones inside the manager; no snapshot at all means a genuinely
        fresh server (trainer init_var repopulates it)."""
        restored = self._snap_mgr.restore_latest(None)
        if restored is None:
            return
        meta = restored["meta"]
        for name, value in restored["values"].items():
            vm = meta["vars"].get(name, {})
            self.vars[name] = _VarState(np.asarray(value),
                                        vm.get("opt_descs", []),
                                        vm.get("grad_name"))
        self.aux = {n: np.asarray(v) for n, v in restored["aux"].items()}
        self.aux_owner = dict(meta.get("aux_owner", {}))
        self._generation = int(meta.get("generation", 0))
        self._snap_step = int(meta.get("snap_step", 0)) + 1
        _events.emit("ps_failover", action="restored",
                     endpoint=f"{self.host}:{self.port}",
                     vars=len(self.vars), aux=len(self.aux),
                     generation=self._generation,
                     snap_step=self._snap_step - 1)
        _log.info("ps[%s:%d]: restored %d vars + %d aux from committed "
                  "snapshot (generation %d)", self.host, self.port,
                  len(self.vars), len(self.aux), self._generation)

    def _snapshot_loop(self, every_s: float):
        while not self._snap_stop.wait(every_s):
            if not self._dirty.is_set():
                continue
            try:
                self.snapshot()
            except Exception as e:  # noqa: BLE001 — a failed periodic
                # snapshot must not kill the serving thread; the manager
                # already counted/evented the failure path
                _log.warning("ps[%s:%d]: periodic snapshot failed "
                             "(%s: %s)", self.host, self.port,
                             type(e).__name__, e)

    # -- optimize-block execution (shared op registry) ---------------------

    def _np_fast_opt(self, od: dict, env: Dict[str, Any]) -> bool:
        """Pure-numpy fast path for the common optimize descs (sgd, adam,
        momentum) — mirrors ops/optimizer_ops.py exactly. The generic
        per-desc jax-eager path pays a dispatch per push, which
        dominates the async server's apply-per-arrival mode; numpy does
        the same math memory-bound."""
        t = od["type"]
        if t not in ("sgd", "adam", "momentum"):
            return False
        ins, outs, attrs = od["inputs"], od["outputs"], od.get("attrs", {})

        def gi(slot):
            names = ins.get(slot) or []
            return env.get(names[0]) if names else None

        def so(slot, val):
            names = outs.get(slot) or []
            if names and names[0]:
                env[names[0]] = val

        from . import native_opt

        p = np.asarray(gi("Param"))
        g = np.asarray(gi("Grad"))
        lr = float(np.asarray(gi("LearningRate")).reshape(-1)[0])
        nlib = native_opt.get_lib()
        if t == "sgd":
            pc, gc = native_opt.f32c(p), native_opt.f32c(g)
            if nlib is not None and pc is not None and gc is not None:
                so("ParamOut", native_opt.sgd(nlib, pc, gc, lr))
            else:
                so("ParamOut", p - lr * g.astype(p.dtype))
            return True
        if t == "momentum":
            v = np.asarray(gi("Velocity"))
            mu = float(attrs.get("mu", 0.9))
            nes = bool(attrs.get("use_nesterov", False))
            pc, gc, vc = (native_opt.f32c(p), native_opt.f32c(g),
                          native_opt.f32c(v))
            if nlib is not None and pc is not None and gc is not None \
                    and vc is not None:
                # fused kernel mutates v in place; the same array is the
                # VelocityOut write-back
                so("ParamOut", native_opt.momentum(nlib, pc, gc, vc, lr,
                                                   mu, nes))
                so("VelocityOut", vc)
                return True
            v_new = mu * v + g
            if nes:
                p_new = p - (g + mu * v_new) * lr
            else:
                p_new = p - lr * v_new
            so("ParamOut", p_new)
            so("VelocityOut", v_new)
            return True
        # adam
        m1 = np.asarray(gi("Moment1"))
        m2 = np.asarray(gi("Moment2"))
        b1p_arr = np.asarray(gi("Beta1Pow"))
        b2p_arr = np.asarray(gi("Beta2Pow"))
        b1 = np.float32(attrs.get("beta1", 0.9))
        b2 = np.float32(attrs.get("beta2", 0.999))
        eps = float(attrs.get("epsilon", 1e-8))
        cands = [native_opt.f32c(a) for a in (p, g, m1, m2, b1p_arr,
                                              b2p_arr)]
        if nlib is not None and all(a is not None for a in cands):
            pc, gc, m1c, m2c, b1c, b2c = cands
            # single fused pass (native/src/psopt.cc): moments and beta
            # pows update in place — the same arrays are the write-backs
            so("ParamOut", native_opt.adam(nlib, pc, gc, m1c, m2c, b1c,
                                           b2c, lr, float(b1), float(b2),
                                           eps))
            so("Moment1Out", m1c)
            so("Moment2Out", m2c)
            so("Beta1PowOut", b1c)
            so("Beta2PowOut", b2c)
            return True
        b1p = b1p_arr.reshape(-1)[0]
        b2p = b2p_arr.reshape(-1)[0]
        m1n = b1 * m1 + (1 - b1) * g
        m2n = b2 * m2 + (1 - b2) * np.square(g)
        lr_t = np.float32(lr) * np.sqrt(1 - b2p) / (1 - b1p)
        so("ParamOut", (p - lr_t * m1n / (np.sqrt(m2n) + eps))
           .astype(p.dtype))
        so("Moment1Out", m1n)
        so("Moment2Out", m2n)
        # accumulator dtype preserved, product in array dtype (parity with
        # the registry adam kernel's b1p * b1)
        so("Beta1PowOut", b1p_arr * b1p_arr.dtype.type(b1))
        so("Beta2PowOut", b2p_arr * b2p_arr.dtype.type(b2))
        return True

    def _run_opt(self, vs: _VarState, name: str, grad: np.ndarray):
        """Run the param's shipped optimize OpDescs eagerly on CPU."""
        import jax

        from ..core import registry
        from ..core.ir import OpDesc
        from ..core.registry import KernelCtx

        env: Dict[str, Any] = {name: vs.value, name + "@GRAD": grad}
        if vs.grad_name:
            env[vs.grad_name] = grad
        env.update(self.aux)
        for od in vs.opt_descs:
            if self._np_fast_opt(od, env):
                continue
            op = OpDesc.from_dict(od)
            opdef = registry.get_op_def(op.type)
            ins = {slot: [env.get(n) for n in names]
                   for slot, names in op.inputs.items()}
            ctx = KernelCtx(op)
            outs = opdef.call(ins, op.attrs, ctx)
            for slot, names in op.outputs.items():
                vals = outs.get(slot, [])
                for i, n in enumerate(names):
                    if n and i < len(vals) and vals[i] is not None:
                        env[n] = vals[i]
        vs.value = np.asarray(env[name])
        # write back ONLY the aux vars this param's optimize ops output —
        # writing the whole env snapshot would clobber concurrent handlers'
        # fresh moments with stale copies (async mode races)
        written = set()
        for od in vs.opt_descs:
            for names in od["outputs"].values():
                written.update(n for n in names if n)
        for k in written:
            if k in self.aux and k in env:
                self.aux[k] = np.asarray(env[k])

    # -- request handlers (reference: request_handler_impl.cc) -------------

    def handle(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Envelope wrapper around `_handle_enveloped`: strips the
        tracing envelope field and — when the client's call was part of
        a SAMPLED trace — opens a server-side child span, so the
        cross-process trace tree shows trainer step → ps.rpc →
        ps.server.<op> with server-side time attributed (the role of
        the reference's profiler events inside the RPC request
        handlers). Untraced frames skip straight through."""
        tp = msg.pop(TRACE_FIELD, None) if isinstance(msg, dict) else None
        tctx = _tracing.parse_traceparent(tp) if tp else None
        if tctx is None or not tctx.sampled:
            return self._handle_enveloped(msg)
        with _tracing.trace_span(
                f"ps.server.{msg.get('op', '?')}", cat="ps", ctx=tctx,
                endpoint=f"{self.host}:{self.port}"):
            return self._handle_enveloped(msg)

    def _handle_enveloped(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Chaos injection point (`ps_server[=index]:crash` fires here,
        modeling a server dying mid-service), retried-request dedupe
        for (cid, seq)-stamped frames, and dirty tracking for the
        periodic snapshot thread."""
        _faults.check("ps_server", step=self.server_index)
        cid = msg.get(CID_FIELD)
        if cid is None:
            out = self._handle(msg)
            if msg.get("op") in self._MUTATING_OPS and "error" not in out:
                self._dirty.set()
            return out
        seq = msg.get(SEQ_FIELD)
        op = str(msg.get("op", "?"))
        with self._reply_lock:
            cached = self._reply_cache.get(cid)
            if cached is not None and cached[0] == seq:
                # a retry of the call whose reply was lost: answer from
                # the cache, do NOT re-apply
                self._reply_cache.move_to_end(cid)
                DEDUP_REPLIES.inc(op=op)
                return cached[1]
        inner = {k: v for k, v in msg.items()
                 if k not in (CID_FIELD, SEQ_FIELD)}
        out = self._handle(inner)
        if op in self._MUTATING_OPS and "error" not in out:
            self._dirty.set()
            # only MUTATING replies enter the cache: re-executing a
            # retried pull is safe (idempotent) and caching it would
            # pin the last multi-MB parameter reply per connection in
            # server memory. Leaving the previous mutating entry in
            # place is also safe — calls per conn are serialized, so a
            # retry of seq N can only arrive before seq N+1 was issued.
            with self._reply_lock:
                self._reply_cache[cid] = (seq, out)
                self._reply_cache.move_to_end(cid)
                while len(self._reply_cache) > self._REPLY_CACHE_CIDS:
                    self._reply_cache.popitem(last=False)
        return out

    def _handle(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        op = msg["op"]
        if op == "init_var":
            name = msg["name"]
            self.vars[name] = _VarState(np.asarray(msg["value"]),
                                        msg.get("opt_descs", []),
                                        msg.get("grad_name"))
            return {"ok": True}
        if op == "init_aux_many":
            for n, v in zip(msg["names"], msg["values"]):
                self.aux[n] = np.asarray(v)
            return {"ok": True}
        if op == "init_aux":
            self.aux[msg["name"]] = np.asarray(msg["value"])
            if msg.get("owner"):
                self.aux_owner[msg["name"]] = msg["owner"]
            return {"ok": True}
        if op == "get":
            vs = self.vars.get(msg["name"])
            if vs is None:
                return {"error": f"unknown var {msg['name']}"}
            if self.mode == "sync":
                # get-barrier: serve only after the current step applied
                gen = msg.get("generation", 0)
                with self._step_done:
                    ok = self._step_done.wait_for(
                        lambda: self._generation >= gen, timeout=120)
                if not ok:
                    return {"error":
                            f"sync get-barrier timeout: generation "
                            f"{self._generation} < requested {gen} (a peer "
                            f"trainer is likely dead or wedged)"}
            # lock-id: ps.server._VarState.lock
            with vs.lock:
                if self.mode == "async" and self.dc_lambda > 0.0:
                    self._pull_snapshots[(msg.get("trainer_id", 0),
                                          msg["name"])] = vs.value.copy()
                return {"value": vs.value}
        if op == "send_grad":
            tid = msg.get("trainer_id", 0)
            self.monitor.beat(tid)
            name = msg["name"]
            vs = self.vars.get(name)
            if vs is None:
                return {"error": f"unknown var {name}"}
            grad = np.asarray(msg["grad"])
            if self.mode == "async":
                # lock-id: ps.server._VarState.lock
                with vs.lock:
                    if self.dc_lambda > 0.0:
                        bak = self._pull_snapshots.get((tid, name))
                        if bak is not None:
                            grad = grad + self.dc_lambda * grad * grad * \
                                (vs.value - bak)
                    self._run_opt(vs, name, grad)
            else:  # sync: hold per-trainer until barrier (resend replaces)
                # lock-id: ps.server._VarState.lock
                with vs.lock:
                    vs.recv[tid] = grad
            return {"ok": True}
        if op == "send_grads":
            # merged dense send (communicator.h:276 merged sends): one
            # RPC carries every grad placed on this server, amortizing
            # the per-RPC round trip across vars
            tid = msg.get("trainer_id", 0)
            for name, grad in zip(msg["names"], msg["grads"]):
                out = self.handle({"op": "send_grad", "name": name,
                                   "grad": grad, "trainer_id": tid})
                if "error" in out:
                    return out
            return {"ok": True}
        if op == "get_many":
            # merged dense pull (parameter_recv.cc batches recvs per
            # endpoint); in sync mode only the first name pays the
            # get-barrier wait — the rest observe the same generation
            values = []
            for name in msg["names"]:
                out = self.handle({"op": "get", "name": name,
                                   "generation": msg.get("generation", 0),
                                   "trainer_id": msg.get("trainer_id", 0)})
                if "error" in out:
                    return out
                values.append(out["value"])
            return {"values": values}
        if op == "send_delta":  # GEO-SGD (communicator.h:323)
            name = msg["name"]
            vs = self.vars.get(name)
            if vs is None:
                return {"error": f"unknown var {name}"}
            # lock-id: ps.server._VarState.lock
            with vs.lock:
                vs.value = vs.value + np.asarray(msg["delta"])
            return {"ok": True}
        if op == "send_barrier":
            # all grads of this trainer are in; when every trainer has
            # barriered, apply optimize blocks (RunSyncLoop :110). The
            # barrier is a SET of trainer ids — a re-sent barrier from a
            # rejoined trainer cannot double-count.
            tid = int(msg.get("trainer_id", 0))
            with self._barrier_lock:
                self._send_barrier.add(tid)
                if len(self._send_barrier) >= self.num_trainers:
                    self._send_barrier.clear()
                    for name, vs in self.vars.items():
                        # lock-id: ps.server._VarState.lock
                        with vs.lock:
                            if vs.recv:
                                g = (sum(vs.recv.values())
                                     / max(len(vs.recv), 1))
                                self._run_opt(vs, name, g)
                                vs.recv.clear()
                    self._generation += 1
                    self._step_done.notify_all()
            return {"ok": True, "generation": self._generation}
        if op == "pull_sparse":
            vs = self.vars.get(msg["name"])
            if vs is None:
                return {"error": f"unknown var {msg['name']}"}
            ids = np.asarray(msg["ids"]).reshape(-1)
            if ids.size and (ids.min() < 0 or ids.max() >= len(vs.value)):
                return {"error": f"sparse id out of range for "
                                 f"{msg['name']}: [{ids.min()}, {ids.max()}] "
                                 f"vs {len(vs.value)} local rows"}
            # lock-id: ps.server._VarState.lock
            with vs.lock:  # torn reads vs concurrent push_sparse_grad
                return {"rows": vs.value[ids].copy()}
        if op == "push_sparse_grad":
            vs = self.vars.get(msg["name"])
            if vs is None:
                return {"error": f"unknown var {msg['name']}"}
            ids = np.asarray(msg["ids"]).reshape(-1)
            if ids.size and (ids.min() < 0 or ids.max() >= len(vs.value)):
                return {"error": f"sparse id out of range for "
                                 f"{msg['name']}: [{ids.min()}, {ids.max()}] "
                                 f"vs {len(vs.value)} local rows"}
            grads = np.asarray(msg["grads"])
            lr = float(msg.get("lr", 0.01))
            # lock-id: ps.server._VarState.lock
            with vs.lock:
                np.subtract.at(vs.value, ids, lr * grads)
            return {"ok": True}
        if op == "heartbeat":
            self.monitor.beat(msg["trainer_id"], msg.get("state"))
            return {"ok": True}
        if op == "rejoin":
            # elastic rejoin (reference: listen_and_serv_op.cc:178-179
            # ResetReceivedVars): a restarted trainer re-registers; the
            # dead incarnation's partial step state is discarded so the
            # new one can't double-contribute, and the current generation
            # is returned so it resumes pulls at the live step. Peers
            # blocked in the get-barrier are untouched: the rejoined
            # trainer's next send+barrier completes the pending step.
            tid = int(msg["trainer_id"])
            with self.monitor._lock:
                self.monitor.states[tid] = HeartBeatMonitor.RUNNING
                self.monitor.last_beat[tid] = time.time()
                if tid in self.monitor.lost:
                    self.monitor.lost.remove(tid)
            with self._barrier_lock:
                self._send_barrier.discard(tid)
            for vname, vs in list(self.vars.items()):
                # lock-id: ps.server._VarState.lock
                with vs.lock:
                    vs.recv.pop(tid, None)
                    # drop the dead incarnation's DC-ASGD pull snapshot:
                    # compensating the reborn trainer's first push against
                    # it would inject a wildly stale (w_now - w_at_pull)
                    self._pull_snapshots.pop((tid, vname), None)
            return {"ok": True, "generation": self._generation}
        if op == "has_var":
            return {"ok": msg["name"] in self.vars}
        if op == "all_completed":
            with self.monitor._lock:
                done = all(s == HeartBeatMonitor.COMPLETED
                           for s in self.monitor.states.values())
            return {"ok": done}
        if op == "barrier_ping":
            return {"generation": self._generation}
        if op == "checkpoint_notify":
            # reference: checkpoint_notify_op -> pserver checkpoint block
            # (distribute_transpiler.py:1813): persist every local var
            # (params + optimizer aux) as save_vars-format .npy files.
            # Aux accumulators save under their owner param's lock so each
            # shard is step-consistent; disk errors reply as {"error"}
            # instead of killing the connection.
            import os

            from ..io import var_filename

            try:
                dirname = msg["dirname"]
                os.makedirs(dirname, exist_ok=True)
                saved = []
                owned_aux: Dict[str, list] = {}
                for an, owner in self.aux_owner.items():
                    owned_aux.setdefault(owner, []).append(an)
                from ..resilience import atomic as _atomic

                for name, vs in list(self.vars.items()):
                    # lock-id: ps.server._VarState.lock
                    with vs.lock:
                        _atomic.np_save(
                            os.path.join(dirname, var_filename(name)),
                            vs.value)
                        for an in owned_aux.get(name, []):
                            if an in self.aux:
                                _atomic.np_save(os.path.join(
                                    dirname, var_filename(an)),
                                    np.asarray(self.aux[an]))
                                saved.append(an)
                    saved.append(name)
                for an, val in list(self.aux.items()):
                    if an not in saved:   # ownerless aux: best effort
                        _atomic.np_save(
                            os.path.join(dirname, var_filename(an)),
                            np.asarray(val))
                        saved.append(an)
                return {"ok": True, "saved": saved}
            except OSError as e:
                return {"error": f"checkpoint failed: {e}"}
        if op == "shuffle_begin":
            # first trainer of a round opens a new pass: fresh seed,
            # fresh per-target buffers. Idempotent per (pass, trainer).
            tid = int(msg["trainer_id"])
            with self._shuf_cv:
                # a trainer may lap its peers: if it already TOOK its
                # shard of the current pass, this begin wants the NEXT
                # pass — block until every trainer has taken (rollover
                # clears all sets). A begin from a trainer still inside
                # the current pass (retry) falls through idempotently.
                ok = self._shuf_cv.wait_for(
                    lambda: tid not in self._shuf_taken, timeout=120)
                if not ok:
                    return {"error": "shuffle_begin barrier timeout: a "
                                     "peer never took its shard"}
                if not self._shuf_begun:
                    self._shuf_pass += 1
                    self._shuf_seed = int(
                        np.random.SeedSequence(
                            [self._shuf_pass, 0x5EED]).generate_state(1)[0])
                    self._shuf_buf = {t: [] for t in
                                      range(self.num_trainers)}
                    self._shuf_done.clear()
                    self._shuf_taken.clear()
                self._shuf_begun.add(tid)
                # snapshot under the cv: if a peer's timeout aborts this
                # pass and another begin re-seeds it before we build the
                # response, reading the attributes outside the lock would
                # hand this trainer a different pass's seed and break the
                # exactly-once partition
                seed, pass_id = self._shuf_seed, self._shuf_pass
            return {"seed": seed, "pass_id": pass_id}
        if op == "shuffle_put":
            target = int(msg["target"])
            if not (0 <= target < self.num_trainers):
                return {"error": f"shuffle target {target} out of range"}
            recs = np.asarray(msg["records"], np.float32)
            with self._shuf_cv:
                if target not in self._shuf_buf:
                    return {"error": "no active shuffle pass (aborted?) — "
                                     "call shuffle_begin again"}
                self._shuf_buf[target].append(recs)
            return {"ok": True}
        if op == "shuffle_done":
            with self._shuf_cv:
                self._shuf_done.add(int(msg["trainer_id"]))
                self._shuf_cv.notify_all()
            return {"ok": True}
        if op == "shuffle_take":
            tid = int(msg["trainer_id"])
            with self._shuf_cv:
                ok = self._shuf_cv.wait_for(
                    lambda: len(self._shuf_done) >= self.num_trainers,
                    timeout=120)
                if not ok:
                    # ABORT the pass: a peer died mid-route. Clearing all
                    # state here means a retry opens a fresh pass and
                    # re-puts from scratch — leaving the half-routed
                    # buffers would hand out duplicated records on retry.
                    self._shuf_begun.clear()
                    self._shuf_done.clear()
                    self._shuf_taken.clear()
                    self._shuf_buf = {}
                    self._shuf_cv.notify_all()
                    return {"error": "shuffle_take barrier timeout: a "
                                     "peer trainer never finished routing; "
                                     "pass aborted — retry re-routes from "
                                     "scratch"}
                parts = self._shuf_buf.get(tid, [])
                out = (np.concatenate(parts, axis=0) if parts
                       else np.zeros((0, 0), np.float32))
                self._shuf_buf[tid] = []
                self._shuf_taken.add(tid)
                if len(self._shuf_taken) >= self.num_trainers:
                    # rollover: next begin opens a fresh pass, and lapped
                    # trainers blocked in shuffle_begin may proceed
                    self._shuf_begun.clear()
                    self._shuf_taken.clear()
                    self._shuf_cv.notify_all()
            return {"records": out, "pass_id": self._shuf_pass}
        if op == "snapshot":
            # on-demand committed snapshot (the trainer's checkpoint
            # cadence rides this; see PSClient.snapshot_servers)
            if self._snap_mgr is None:
                return {"ok": False, "reason": "no snapshot dir"}
            try:
                d = self.snapshot()
                return {"ok": True, "dir": d, "step": self._snap_step - 1}
            except (OSError, ValueError) as e:
                return {"error": f"snapshot failed: "
                                 f"{type(e).__name__}: {e}"}
        if op == "shutdown":
            threading.Thread(target=self.stop, daemon=True).start()
            return {"ok": True}
        return {"error": f"unknown op {op}"}

    # -- socket plumbing ----------------------------------------------------

    def serve_forever(self):
        ps = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    while True:
                        msg = recv_msg(self.request)
                        send_msg(self.request, ps.handle(msg))
                except (ConnectionError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((self.host, self.port), Handler)
        self._server.serve_forever()

    def start_background(self):
        # warm the fused optimizer library OFF the serving path: a lazy
        # first-use compile inside the barrier critical section would
        # stall every trainer's step-1 barrier for the g++ duration
        from . import native_opt

        threading.Thread(target=native_opt.get_lib, daemon=True).start()
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        # wait for the socket to bind
        for _ in range(100):
            try:
                s = socket.create_connection((self.host, self.port), 0.2)
                s.close()
                return t
            except OSError:
                time.sleep(0.05)
        raise RuntimeError(f"pserver failed to bind {self.host}:{self.port}")

    def stop(self):
        self.monitor.stop()
        self._snap_stop.set()
        if self._snap_thread is not None:
            self._snap_thread.join(timeout=10)
            self._snap_thread = None
        if self._server is not None:
            self._server.shutdown()
            # release the listening socket too: a respawned server (the
            # failover path) must be able to rebind this endpoint
            self._server.server_close()
            self._server = None
