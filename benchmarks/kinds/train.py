"""Runner of every traffic file of kind `train`: the program's
`make_train_step` under the cell's mesh, a window of whole chunks, the
rate over all of it, and the records the per-layer readers take their
numbers from; a traced run also reads the layer scopes of the step's
device ops. Nothing here names a configuration or a cell."""

from __future__ import annotations

import collections
import json
import os
import time
from typing import Dict

from ..harness import device, manifest, program_trace, trace_reduce, window

PREFETCH = 2            # batches resident on the device ahead of the step
REF_MICROBATCH = 64     # sequences per pass of the float32 reference
REF_FORWARD = 32        # sequences whose encoder output is compared


def run(cell: Dict, args, out_dir: str, allow_cpu: bool = False) -> Dict:
    import jax
    import numpy as np
    import optax

    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    dev, peaks = device.start(cell["chips"], allow_cpu)
    config, traffic = cell["config_file"], cell["traffic_file"]
    family = manifest.plugin("families", config["family"])
    model = config["model"]
    cfg = family.make_config(model)
    devices = jax.devices()[:cell["chips"]]
    mesh = make_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    global_batch = traffic["batch_per_chip"] * len(devices)
    tokens_per_step = family.tokens_per_batch(traffic, global_batch)
    k = int(traffic["chunk_steps"])
    ann = jax.profiler.TraceAnnotation

    with mesh_guard(mesh):
        params, axes = family.init(cfg, args.seed)
        loss = family.loss_fn(cfg)
        train = config["train"]
        init_state, step = make_train_step(
            loss, optax.adamw(train["learning_rate"]), mesh, axes,
            strategy=TrainStrategy(**train["strategy"]))
        state = init_state(params)
        del params

        host = family.host_batches(model, traffic, global_batch, args.seed)
        batch_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("dp"))
        queue: collections.deque = collections.deque()
        first_host = next(host)
        queue.append(jax.device_put(first_host, batch_sharding))
        while len(queue) < PREFETCH:
            queue.append(jax.device_put(next(host), batch_sharding))

        # correct, part 1 (before any step: the step donates the state):
        # the program's own loss, dropout off, against the plain float32
        # reference on the same parameters and the same first batch
        got = float(jax.jit(lambda p, b: loss(p, b, None, True))(
            state.params, queue[0]))
        want = family.reference_loss(state.params, model, first_host,
                                     min(REF_MICROBATCH, global_batch))
        ref_rel = abs(got - want) / abs(want)
        # ... and the encoder's output itself: at random weights the loss
        # hardly depends on the encoder, its output does
        fwd_rel = family.forward_gap(state.params, cfg, model, first_host,
                                     min(REF_FORWARD, global_batch))

        base_key = jax.random.key((args.seed + 1) % (2 ** 31))
        losses = []
        input_s = [0.0]

        def run_chunk(chunk_idx: int):
            nonlocal state
            keys = jax.random.split(jax.random.fold_in(base_key, chunk_idx),
                                    k)
            for j in range(k):
                t = time.perf_counter()
                with ann("input_next"):
                    batch = queue.popleft()
                input_s[0] += time.perf_counter() - t
                with ann("dispatch"):
                    state, out = step(state, batch, keys[j])
                losses.append(out)
                t = time.perf_counter()
                with ann("input_next"):
                    queue.append(jax.device_put(next(host), batch_sharding))
                input_s[0] += time.perf_counter() - t
            with ann("sync"):
                jax.block_until_ready(out)

        if args.trace:
            # a traced run reads the step's layer scopes, which are HLO
            # metadata: from here on (the step's first compile) the cache
            # keys on metadata, so an executable cached before a scope
            # changed is never loaded (for the rest of this process, which
            # run.py ends with the run). The reference's programs above,
            # untraced runs and `setup_s` keep the keys they have.
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
        # warm-up: one whole chunk runs every program of the window (step,
        # key split, slices), so that nothing compiles inside it
        run_chunk(0)

        compiles0 = device.COUNTS["compile_requests"]
        input_s[0] = 0.0
        chunks = []
        t_open = time.perf_counter()
        setup_s = t_open - args.t_start
        while True:
            c0 = time.perf_counter()
            run_chunk(len(chunks) + 1)
            c1 = time.perf_counter()
            chunks.append({"chunk": len(chunks), "t0_s": c0 - t_open,
                           "seconds": c1 - c0, "steps": k,
                           "tokens": k * tokens_per_step})
            if c1 - t_open >= args.seconds and len(chunks) >= 2:
                break
        window_s = c1 - t_open
        window_compiles = device.COUNTS["compile_requests"] - compiles0
        input_wait_s = input_s[0]

        trace = scopes = None
        if args.trace:
            trace_dir = os.path.join(out_dir, "trace")
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profile_options())
            t0 = time.perf_counter()
            run_chunk(len(chunks) + 1)
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.reduce_loaded(trace_reduce.load_xplane(
                xplane, ("dispatch", "sync", "input_next")), "harness_other")
            trace["traced_wall_s"] = traced_s
            scopes = program_trace.reduce_scopes(xplane)
            with open(os.path.join(out_dir, "device_scopes.json"),
                      "w") as f:
                json.dump(scopes, f, indent=1)
            if not scopes.get("scoped_ops"):
                raise RuntimeError(
                    "no device op of the trace carries a layer scope: no "
                    "device in the trace, or the step lost its scopes")

        loss_host = [float(x) for x in losses]
        for c, lo in zip(chunks, range(k, len(loss_host), k)):
            c["loss_mean"] = float(np.mean(loss_host[lo:lo + k]))
        # the compiler's plan of the step program: temporaries are not in
        # the runtime's peak on this backend
        plan = device.planned_bytes(step.lower(
            state, queue[0], base_key).compile())
        resident = device.resident_bytes(devices)
        planned_total = resident + plan.get("temp", 0) + max(
            0, plan.get("output", 0) - plan.get("alias", 0))

    rates = window.chunk_rates(chunks, window_s)
    first_mean = float(np.mean(loss_host[:k]))
    last_mean = chunks[-1]["loss_mean"]
    checks = {
        "memory": {"resident_bytes": resident, "plan": plan},
        "reference_loss": want, "program_loss": got,
        "loss_rel_diff": ref_rel, "loss_rel_tol": config["loss_rel_tol"],
        "forward_rel_diff": fwd_rel,
        "forward_rel_tol": config["forward_rel_tol"],
        "losses_finite": bool(np.all(np.isfinite(loss_host))),
        "first_chunk_loss": first_mean, "last_chunk_loss": last_mean,
        "compiles_in_window": window_compiles,
    }
    correct = (ref_rel <= config["loss_rel_tol"]
               and fwd_rel <= config["forward_rel_tol"]
               and checks["losses_finite"]
               and last_mean < first_mean and window_compiles == 0)

    with open(os.path.join(out_dir, "chunks.jsonl"), "w") as f:
        for c in chunks:
            f.write(json.dumps(c) + "\n")

    records = {
        "kind": "train", "chips": len(devices), "peaks": peaks,
        "window_s": window_s, "chunks": chunks,
        "rates": rates, "input_wait_s": input_wait_s,
        "tokens_per_step": tokens_per_step,
        "flops_per_token": family.train_flops_per_token(model, traffic),
        "planned_bytes": planned_total, "trace": trace,
        "scopes": scopes,
    }
    dev["memory_peak_bytes"] = int(max(planned_total,
                                       device.runtime_peak_bytes(devices)))
    return {
        "correct": bool(correct),
        "attempted": sum(c["steps"] for c in chunks), "failed": 0,
        "end_to_end": {"train_tokens_per_s": rates["tokens_per_s"],
                       "setup_s": setup_s},
        "records": records, "device": dev, "checks": checks,
    }
