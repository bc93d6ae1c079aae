"""Benchmark ladder: one JSON line per BASELINE.json training config.

Configs (BASELINE.json "configs"): MNIST LeNet Program-surface smoke,
ResNet-50/ImageNet, Transformer-big NMT, BERT long-sequence (T=4096),
BERT-base pretrain — fwd+bwd+optimizer step throughput on one chip.
Each line: {"metric", "value", "unit", "vs_baseline", "detail"}.
vs_baseline = achieved MFU / 0.50 for the training configs (the
north-star from BASELINE.json: >=50% MFU on v5e; the reference
publishes no TPU training numbers, so the target ratio is the
comparison point); the LeNet smoke line instead reports a 0/1
convergence flag (unit samples/s through the fluid Program/Executor
pipeline). BASELINE config 5 (ResNet-50 DP on v5e-8) needs 8 real
chips and is validated by dryrun_multichip + the ParallelExecutor
parity tests instead. The flagship BERT line prints LAST.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax

# Fast counter-based PRNG: threefry costs ~25% of the BERT step (dropout
# masks); rbg is the standard choice for TPU training loops.
jax.config.update("jax_default_prng_impl", "unsafe_rbg")

import jax.numpy as jnp  # noqa: E402

from paddle_tpu.observability import device_peaks as _peaks  # noqa: E402

# Per-platform peak bf16 matmul throughput per chip — the shared table
# (observability/device_peaks.py) the live MFU gauge uses too, so the
# offline bench MFU and paddle_tpu_mfu agree by construction.
PEAK_FLOPS = _peaks.PLATFORM_PEAK_FLOPS


def _measure(step, state, batch, n_steps):
    """Warmup/compile once, then time n_steps chained steps; the timed
    region ends in block_until_ready on everything the last step
    produced (dispatch is asynchronous)."""
    state, loss = step(state, batch, jax.random.key(2))
    jax.block_until_ready((state, loss))
    t0 = time.perf_counter()
    for i in range(n_steps):
        state, loss = step(state, batch, jax.random.key(3 + i))
    jax.block_until_ready((state, loss))
    dt = time.perf_counter() - t0
    return dt, float(loss)


# Structured run environment attached to EVERY metric line (ROADMAP
# item 5 / VERDICT weak #7): rc=1 with env.fallback_reason recorded on
# the lines means "chip wedged, CPU fallback recorded" — the evidence
# lint (tools/refresh_evidence.py bench_fallback_recorded) can then
# tell that apart from "harness crashed" (no structured lines at all).
# The parent fills the probe verdict into PADDLE_TPU_BENCH_* env vars
# so measurement children agree with it.
_BENCH_ENV = {"platform": None, "tpu_reachable": None,
              "fallback_reason": None}


def _init_bench_env(platform=None):
    reach = os.environ.get("PADDLE_TPU_BENCH_TPU_REACHABLE")
    _BENCH_ENV["platform"] = platform or \
        os.environ.get("PADDLE_TPU_BENCH_PLATFORM")
    _BENCH_ENV["tpu_reachable"] = None if reach is None else reach == "1"
    _BENCH_ENV["fallback_reason"] = \
        os.environ.get("PADDLE_TPU_BENCH_FALLBACK_REASON") or None


def _emit_raw(metric, value, unit, vs_baseline, detail):
    print(json.dumps({"metric": metric, "value": round(value, 2),
                      "unit": unit, "vs_baseline": round(vs_baseline, 4),
                      "env": dict(_BENCH_ENV),
                      "detail": detail}), flush=True)


def _emit(metric, sps_chip, mfu, detail):
    _emit_raw(metric, sps_chip, "samples/s/chip", mfu / 0.50, detail)


def _run_ladder(metric, batch_sizes, build, flops_per_sample, n_steps,
                n_chips, platform, extra_detail, mesh=None):
    """build(bs) -> (step, state, batch); try batch sizes until one fits.
    Tracing/timing runs under mesh_guard so model-level shard() activation
    constraints see the mesh."""
    from paddle_tpu.parallel import mesh_guard
    import contextlib

    last_err = None
    for bs in batch_sizes:
        try:
            guard = mesh_guard(mesh) if mesh is not None \
                else contextlib.nullcontext()
            with guard:
                step, state, batch = build(bs)
                dt, final_loss = _measure(step, state, batch, n_steps)
            sps = bs * n_steps / dt
            mfu = sps * flops_per_sample / (
                n_chips * PEAK_FLOPS.get(platform, 1e12))
            # feed the continuous-attribution layer with the measured
            # window so the LIVE gauge (paddle_tpu_mfu{kind="bench"})
            # and this offline number come from the same sample — the
            # within-10% cross-check PROFILE.md documents
            from paddle_tpu.observability import memwatch as _memwatch
            from paddle_tpu.observability import perfwatch as _perfwatch

            _perfwatch.record_step(
                "bench", dt, flops=bs * n_steps * flops_per_sample,
                n_devices=n_chips,
                device_kind=getattr(jax.devices()[0], "device_kind",
                                    platform))
            mem = _memwatch.sweep(force=True) or {}
            _emit(metric, sps / n_chips, mfu, {
                "batch_size": bs, "chips": n_chips, "platform": platform,
                "mfu": round(mfu, 4),
                "mfu_live": round(_perfwatch.mfu("bench"), 4),
                "hbm_peak_bytes": int(_memwatch.watermark_bytes()),
                "hbm_live_bytes": int(mem.get("total_bytes", 0)),
                "step_ms": round(1000 * dt / n_steps, 2),
                "final_loss": final_loss, **extra_detail,
            })
            return True
        except Exception as e:  # OOM → try smaller batch
            last_err = e
            continue
    print(json.dumps({"metric": metric, "value": 0.0,
                      "unit": "samples/s/chip", "vs_baseline": 0.0,
                      "env": dict(_BENCH_ENV),
                      "error": str(last_err)[:300]}), flush=True)
    return False


def _build_lenet_program(pt):
    """LeNet training Program used by the smoke and pipeline benches."""
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[1, 28, 28], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="int64")
        c = pt.layers.conv2d(x, num_filters=6, filter_size=5, act="relu")
        c = pt.layers.pool2d(c, pool_size=2, pool_stride=2)
        c = pt.layers.conv2d(c, num_filters=16, filter_size=5, act="relu")
        c = pt.layers.pool2d(c, pool_size=2, pool_stride=2)
        h = pt.layers.fc(c, size=120, act="relu")
        h = pt.layers.fc(h, size=84, act="relu")
        logits = pt.layers.fc(h, size=10)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, y))
        pt.optimizer.Adam(learning_rate=2e-3).minimize(loss)
    return main, startup, loss


def bench_lenet_smoke(mesh, n_chips, platform, on_tpu):
    """BASELINE config 1: MNIST LeNet single-chip smoke — the fluid
    Program/Executor surface itself on the chip (feed numpy, fetch a
    converging loss), not the jax-native path. Value is samples/s
    through the FULL Program pipeline; vs_baseline=1.0 marks
    convergence (loss halved), 0.0 otherwise."""
    import numpy as np

    import paddle_tpu as pt

    rng = np.random.RandomState(0)
    X = rng.rand(256, 1, 28, 28).astype("float32")
    Y = rng.randint(0, 10, (256, 1)).astype("int64")
    main, startup, loss = _build_lenet_program(pt)
    place = pt.TPUPlace() if on_tpu else pt.CPUPlace()
    exe = pt.Executor(place)
    try:
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            losses = [float(np.asarray(
                exe.run(main, feed={"x": X, "y": Y},
                        fetch_list=[loss])[0]).reshape(()))]
            n_steps = 80
            t0 = time.perf_counter()
            for _ in range(n_steps):
                losses.append(float(np.asarray(
                    exe.run(main, feed={"x": X, "y": Y},
                            fetch_list=[loss])[0]).reshape(())))
            dt = time.perf_counter() - t0
            cache = exe.cache_stats()
            # chained executable A/B (ROADMAP item 5 / VERDICT weak
            # perf): BENCH_r05 recorded the ROLLED scan-chained path
            # ~2.8x slower per step than per-call on CPU. Profiling
            # showed the while-loop itself is the cost (a pure-jax
            # loop-vs-scan control reproduces 2.6x — XLA-CPU restricts
            # conv parallelism inside while bodies; carry donation was
            # already intact), so run_chained now defaults to "auto":
            # unrolled windows on CPU, rolled scan on TPU. Both sides
            # of the A/B are recorded here: "rolled" is the explicit
            # unroll=False opt-in, "auto" is the new default.
            chain_n = 40

            def time_chained(**kw):
                exe.run_chained(main, feed={"x": X, "y": Y},
                                fetch_list=[loss], n_steps=chain_n,
                                **kw)  # compile
                t0 = time.perf_counter()
                ch = exe.run_chained(main, feed={"x": X, "y": Y},
                                     fetch_list=[loss], n_steps=chain_n,
                                     **kw)
                last = float(np.asarray(ch[0]).ravel()[-1])  # sync
                return time.perf_counter() - t0, last

            rolled_dt, _ = time_chained(unroll=False)
            chain_dt, last = time_chained()  # the "auto" default
    except Exception as e:  # a fluid-path failure must not kill the ladder
        _emit_raw("lenet_mnist_program_smoke_samples_per_sec", 0.0,
                  "samples/s", 0.0, {"error": str(e)[:300]})
        return False
    converged = losses[-1] < losses[0] * 0.5 and last < losses[0] * 0.5
    _emit_raw("lenet_mnist_program_smoke_samples_per_sec",
              256 * n_steps / dt, "samples/s",
              1.0 if converged else 0.0,
              {"platform": platform, "first_loss": round(losses[0], 4),
               "final_loss": round(losses[-1], 4),
               "steps": n_steps, "batch_size": 256,
               "executor_cache": cache,
               "scan_chained_samples_per_sec":
                   round(256 * chain_n / chain_dt, 2),
               "scan_chained_steps": chain_n,
               "chained": {
                   "per_call_samples_per_sec": round(256 * n_steps / dt, 2),
                   "rolled_scan_samples_per_sec":
                       round(256 * chain_n / rolled_dt, 2),
                   "auto_samples_per_sec":
                       round(256 * chain_n / chain_dt, 2),
                   "rolled_slowdown_vs_per_call":
                       round((256 * n_steps / dt)
                             / (256 * chain_n / rolled_dt), 3),
                   "auto_slowdown_vs_per_call":
                       round((256 * n_steps / dt)
                             / (256 * chain_n / chain_dt), 3),
                   "note": "rolled scan (unroll=False) is the BENCH_r05 "
                           "regression, now opt-in on CPU; auto = new "
                           "default (unrolled windows on CPU, rolled "
                           "scan on TPU); donation on the scan carry "
                           "verified intact (pure-jax control "
                           "reproduces the while-loop penalty)"},
               "note": "per-call loop includes the host round trip; "
                       "scan_chained = cached-executable fast path "
                       "(one dispatch covers all steps under "
                       "unroll=auto)"})
    return converged


def bench_pipeline(mesh, n_chips, platform, on_tpu):
    """Host-overlap pipeline block: the SAME LeNet Program trained on
    the same per-step batches by (a) the per-call loop — one dispatch +
    synchronous numpy fetch per step, the pre-async executor rhythm —
    and (b) the streaming driver — run_stream window micro-chaining
    with device prefetch and lazy fetches. Value is streaming
    samples/s; vs_baseline = (streaming/per-call speedup) / 1.5, the
    acceptance bar. detail carries both throughputs plus each phase's
    host-blocked fraction (host_blocked_seconds delta over wall) and
    the final-loss delta proving the drivers compute the same thing."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.observability import telemetry as T

    rng = np.random.RandomState(0)
    # Dispatch-bound regime (the INFER_BENCH/BENCH_r05 failure mode —
    # host round trip ≫ device compute): small per-step batch so the
    # per-call loop's fixed per-step host cost dominates.
    bs, n_steps, window = 1, 128, 16
    X = rng.rand(n_steps, bs, 1, 28, 28).astype("float32")
    Y = rng.randint(0, 10, (n_steps, bs, 1)).astype("int64")
    feeds = [{"x": X[i], "y": Y[i]} for i in range(n_steps)]
    main, startup, loss = _build_lenet_program(pt)
    place = pt.TPUPlace() if on_tpu else pt.CPUPlace()
    exe = pt.Executor(place)

    try:
        # warm every executable on a throwaway scope so neither timed
        # phase pays a compile (the program cache is scope-independent)
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            exe.run(main, feed=feeds[0], fetch_list=[loss])
            for h in exe.run_stream(main, iter(feeds[:window + 1]),
                                    fetch_list=[loss], window=window):
                h.result()

        def phase(streaming):
            with pt.scope_guard(pt.Scope()):
                exe.run(startup)
                blocked0 = T.host_blocked_total()
                t0 = time.perf_counter()
                if streaming:
                    last = None
                    for h in exe.run_stream(main, iter(feeds),
                                            fetch_list=[loss],
                                            window=window):
                        last = h
                    final = float(np.asarray(last.result()[0]).ravel()[-1])
                else:
                    vals = [exe.run(main, feed=f, fetch_list=[loss])[0]
                            for f in feeds]
                    final = float(np.asarray(vals[-1]).reshape(()))
                dt = time.perf_counter() - t0
                blocked = T.host_blocked_total() - blocked0
            return dt, final, blocked

        # best-of-2 per driver: a noisy-neighbor CPU must not decide
        # the speedup gate; losses are identical across repeats by
        # construction (fresh scope, same seed, same feeds)
        percall_dt, percall_loss, percall_blocked = min(
            (phase(False) for _ in range(2)), key=lambda r: r[0])
        stream_dt, stream_loss, stream_blocked = min(
            (phase(True) for _ in range(2)), key=lambda r: r[0])
    except Exception as e:
        _emit_raw("pipeline_stream_samples_per_sec", 0.0, "samples/s",
                  0.0, {"error": str(e)[:300]})
        return False

    percall_sps = bs * n_steps / percall_dt
    stream_sps = bs * n_steps / stream_dt
    speedup = stream_sps / percall_sps
    loss_delta = abs(stream_loss - percall_loss)
    blocked_percall = percall_blocked / percall_dt
    blocked_stream = stream_blocked / stream_dt
    # acceptance: 1.5x throughput, OR proven overlap where the
    # per-call loop is host-bound (blocked > 70% while streaming
    # stays < 30%)
    ok = (speedup >= 1.5
          or (blocked_percall > 0.7 and blocked_stream < 0.3)) \
        and loss_delta <= 1e-6 * max(1.0, abs(percall_loss))
    _emit_raw("pipeline_stream_samples_per_sec", stream_sps, "samples/s",
              speedup / 1.5,
              {"platform": platform, "batch_size": bs, "steps": n_steps,
               "window": window,
               "per_call_samples_per_sec": round(percall_sps, 2),
               "speedup": round(speedup, 3),
               "host_blocked_frac_per_call": round(blocked_percall, 4),
               "host_blocked_frac_stream": round(blocked_stream, 4),
               "final_loss_per_call": round(percall_loss, 6),
               "final_loss_stream": round(stream_loss, 6),
               "loss_delta": loss_delta,
               "note": "per-call = dispatch + sync numpy fetch per "
                       "step; stream = run_stream unrolled-window "
                       "micro-chaining + lazy fetches (device "
                       "prefetch pays off on real TPU transfers, not "
                       "CPU, so the CPU stream phase feeds host "
                       "arrays)"})
    return ok


# ---------------------------------------------------------------------------
# Coldstart block (ISSUE 6): restart economics of the persistent compile
# cache (PADDLE_TPU_COMPILE_CACHE) and the serving warmstart artifact.
# Unlike every other block this one measures PROCESS BOUNDARIES — a cold
# start IS a fresh process — so all jax work happens in measurement
# children and the block's own process never initializes a backend (on
# TPU it would hold the chip its children need to boot).
# ---------------------------------------------------------------------------


def _coldstart_child(argv):
    """`bench.py --coldstart-child MODE ...`: one fresh-process
    measurement for bench_coldstart.

    prep  --model-dir D      save the small serving softmax model
    train --steps N          LeNet per-call + chained steps under the
                             inherited PADDLE_TPU_COMPILE_CACHE
    serve --model-dir D --buckets B --artifact A [--load-artifact]
                             boot a serving Engine, warm every bucket,
                             answer one fixed batch; cold mode exports
                             the warmstart artifact, warm mode boots
                             from it

    Prints ONE JSON line: compile/cache telemetry deltas plus losses
    (train) or the reply digest (serve). The parent measures child wall
    time itself; in-child timings cover only the phase being claimed
    (serve's warmup window = time-to-first-healthy)."""
    import argparse
    import hashlib

    import numpy as np

    ap = argparse.ArgumentParser(prog="bench --coldstart-child")
    ap.add_argument("mode", choices=("prep", "train", "serve"))
    ap.add_argument("--model-dir")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--buckets", default="1,2,4,8")
    ap.add_argument("--artifact")
    ap.add_argument("--load-artifact", action="store_true")
    args = ap.parse_args(argv)

    if os.environ.get("PADDLE_TPU_BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    # cold vs warm here is about the repo's OWN cache and warmstart
    # artifact: with JAX's persistent cache on (JAX_COMPILATION_CACHE_DIR
    # is inherited), a "cold" child would measure a warm start
    jax.config.update("jax_enable_compilation_cache", False)
    import paddle_tpu as pt
    from paddle_tpu import observability

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"

    def _telemetry_summary():
        """This process's compile seconds, total and per kind (the
        ISSUE acceptance measure: paddle_tpu_compile_seconds — cache
        hits record NO compile, so a fully-warm process sums to zero),
        plus the compile-cache outcome counts."""
        snap = observability.snapshot()
        comp = snap.get("paddle_tpu_compile_seconds") or {"series": []}
        cache = snap.get("paddle_tpu_compile_cache_total") \
            or {"series": []}
        outcomes = {}
        for s in cache["series"]:
            ev = s["labels"].get("event", "?")
            outcomes[ev] = outcomes.get(ev, 0) + int(s["value"])
        by_kind: dict = {}
        counts_by_kind: dict = {}
        for s in comp["series"]:
            k = s["labels"].get("kind", "?")
            by_kind[k] = round(by_kind.get(k, 0.0) + s["sum"], 4)
            counts_by_kind[k] = counts_by_kind.get(k, 0) + s["count"]
        return {
            "compile_seconds": round(
                sum(s["sum"] for s in comp["series"]), 4),
            "compiles": int(sum(s["count"] for s in comp["series"])),
            "compile_seconds_by_kind": by_kind,
            "compiles_by_kind": counts_by_kind,
            "cache_events": outcomes,
        }

    if args.mode == "prep":
        main, startup = pt.Program(), pt.Program()
        with pt.framework.unique_name.guard(), \
                pt.program_guard(main, startup):
            x = pt.layers.data(name="x", shape=[4], dtype="float32")
            pred = pt.layers.fc(input=x, size=3, act="softmax")
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        pt.io.save_inference_model(args.model_dir, ["x"], [pred], exe,
                                   main_program=main)
        print(json.dumps({"ok": True}), flush=True)
        return 0

    if args.mode == "train":
        rng = np.random.RandomState(0)
        X = rng.rand(64, 1, 28, 28).astype("float32")
        Y = rng.randint(0, 10, (64, 1)).astype("int64")
        main, startup, loss = _build_lenet_program(pt)
        exe = pt.Executor(pt.TPUPlace() if on_tpu else pt.CPUPlace())
        losses = []
        t0 = time.perf_counter()
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            for _ in range(args.steps):
                losses.append(float(np.asarray(
                    exe.run(main, feed={"x": X, "y": Y},
                            fetch_list=[loss])[0]).reshape(())))
            ch = exe.run_chained(main, feed={"x": X, "y": Y},
                                 fetch_list=[loss], n_steps=4)
            losses.extend(float(v) for v in np.asarray(ch[0]).ravel())
        wall = time.perf_counter() - t0
        print(json.dumps(dict(_telemetry_summary(), platform=platform,
                              losses=losses,
                              run_wall_seconds=round(wall, 4))),
              flush=True)
        return 0

    # serve: time-to-first-healthy = Engine construction (which adopts
    # the warmstart artifact when --load-artifact) through warmup()
    from paddle_tpu.serving import Engine, ServingConfig

    buckets = tuple(int(b) for b in args.buckets.split(","))
    t0 = time.perf_counter()
    cfg = ServingConfig(args.model_dir, buckets=buckets, use_tpu=on_tpu,
                        warmstart=args.artifact if args.load_artifact
                        else None)
    engine = Engine(cfg)
    ready = engine.warmup()
    ttfh = time.perf_counter() - t0
    if args.artifact and not args.load_artifact:
        engine.export_warmstart(args.artifact)
    # batch 2 rides warmed bucket 2 in both smoke and full bucket
    # sets — the reply must not mint a signature the artifact never
    # carried (real traffic is bucket-shaped by the batcher)
    X = np.random.RandomState(7).rand(2, 4).astype("float32")
    out = engine.run_batch({"x": X})
    digest = hashlib.sha256()
    for name in sorted(out):
        a = np.ascontiguousarray(out[name])
        digest.update(f"{name}:{a.dtype}:{a.shape}".encode())
        digest.update(a.tobytes())
    print(json.dumps(dict(
        _telemetry_summary(), platform=platform, buckets_ready=ready,
        warmstart_adopted=engine.warmstart_adopted,
        ttfh_seconds=round(ttfh, 4),
        reply_sha256=digest.hexdigest())), flush=True)
    return 0


def bench_coldstart(smoke=False):
    """Cold vs warm restart, cold vs warm serving boot — each phase a
    fresh subprocess so "restart" means what an operator means by it.

    Emits two metric lines (value = cold/warm ratio of in-process
    paddle_tpu_compile_seconds; acceptance bar 5x, so vs_baseline =
    speedup / 5):

      coldstart_restart_compile_speedup    training process restart
          against the same PADDLE_TPU_COMPILE_CACHE dir; ok requires
          the warm run to report ZERO fresh compiles and bit-identical
          losses.
      coldstart_serving_warmup_compile_speedup   serving boot, cold
          compile vs warmstart-artifact adoption — the value is the
          warmup compile-seconds ratio (the ISSUE acceptance measure);
          detail carries the time-to-first-healthy walls and their own
          ttfh_speedup ratio (smaller: TTFH includes model load and
          adoption I/O) plus the reply digests proving bit-identical
          answers.
    """
    import shutil
    import tempfile

    here = os.path.abspath(__file__)
    base_env = dict(os.environ)
    # the serving phase must prove the ARTIFACT path on its own — an
    # inherited compile-cache dir would warm its "cold" boot
    base_env.pop("PADDLE_TPU_COMPILE_CACHE", None)
    steps = 3 if smoke else 6
    buckets = "1,2" if smoke else "1,2,4,8"
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_coldstart_")

    def child(argv, extra_env=None, timeout_s=300):
        rc, out, err = _run_bounded(
            [sys.executable, here, "--coldstart-child"] + list(argv),
            timeout_s, env=dict(base_env, **(extra_env or {})))
        if rc != 0:
            raise RuntimeError(
                f"coldstart child {argv[0]} rc={rc}: "
                f"{(err or '')[-500:]}")
        lines = [ln for ln in (out or "").splitlines()
                 if ln.startswith("{")]
        if not lines:
            raise RuntimeError(f"coldstart child {argv[0]} emitted no "
                               f"JSON: {(err or '')[-500:]}")
        return json.loads(lines[-1])

    def speedup(cold_s, warm_s):
        # a fully-warm process records NO compiles, so the denominator
        # floor (1 ms) keeps the ratio finite while preserving "huge"
        return cold_s / max(warm_s, 1e-3)

    train_ok = serve_ok = False
    try:
        try:
            cache_dir = os.path.join(tmp, "cache")
            os.makedirs(cache_dir, exist_ok=True)
            cache_env = {"PADDLE_TPU_COMPILE_CACHE": cache_dir}
            targs = ["train", "--steps", str(steps)]
            t0 = time.perf_counter()
            cold = child(targs, cache_env)
            cold_wall = time.perf_counter() - t0
            t0 = time.perf_counter()
            warm = child(targs, cache_env)
            warm_wall = time.perf_counter() - t0
            ratio = speedup(cold["compile_seconds"],
                            warm["compile_seconds"])
            loss_delta = float(max(
                abs(a - b) for a, b in zip(cold["losses"],
                                           warm["losses"])))
            train_ok = (ratio >= 5.0 and loss_delta == 0.0
                        and warm["compiles"] == 0
                        and warm["cache_events"].get("hit", 0)
                        >= cold["compiles"])
            _emit_raw(
                "coldstart_restart_compile_speedup", ratio, "x",
                ratio / 5.0,
                {"platform": cold["platform"], "steps": steps,
                 "cold_compile_seconds": cold["compile_seconds"],
                 "warm_compile_seconds": warm["compile_seconds"],
                 "cold_compiles": cold["compiles"],
                 "warm_compiles": warm["compiles"],
                 "warm_cache_hits": warm["cache_events"].get("hit", 0),
                 "cold_process_wall_s": round(cold_wall, 2),
                 "warm_process_wall_s": round(warm_wall, 2),
                 "loss_delta": loss_delta,
                 "note": "fresh process per phase, shared "
                         "PADDLE_TPU_COMPILE_CACHE dir; process wall "
                         "includes interpreter+jax import, "
                         "compile_seconds is the ISSUE acceptance "
                         "measure"})
        except Exception as e:
            _emit_raw("coldstart_restart_compile_speedup", 0.0, "x",
                      0.0, {"error": str(e)[:300]})

        try:
            model_dir = os.path.join(tmp, "model")
            child(["prep", "--model-dir", model_dir])
            art = os.path.join(tmp, "warmstart.bin")
            sargs = ["serve", "--model-dir", model_dir,
                     "--buckets", buckets, "--artifact", art]
            t0 = time.perf_counter()
            scold = child(sargs)
            scold_wall = time.perf_counter() - t0
            t0 = time.perf_counter()
            swarm = child(sargs + ["--load-artifact"])
            swarm_wall = time.perf_counter() - t0
            # the artifact targets WARMUP compilation (kind="infer" —
            # one executable per bucket); the model-LOAD step program
            # compiles either way and is reported separately in detail
            cold_infer = scold["compile_seconds_by_kind"].get(
                "infer", 0.0)
            warm_infer = swarm["compile_seconds_by_kind"].get(
                "infer", 0.0)
            ratio = speedup(cold_infer, warm_infer)
            identical = (scold["reply_sha256"] == swarm["reply_sha256"])
            n_buckets = len(buckets.split(","))
            serve_ok = (ratio >= 5.0 and identical
                        and swarm["warmstart_adopted"] == n_buckets
                        and swarm["compiles_by_kind"].get("infer", 0)
                        == 0)
            _emit_raw(
                "coldstart_serving_warmup_compile_speedup", ratio, "x",
                ratio / 5.0,
                {"platform": scold["platform"], "buckets": buckets,
                 "cold_warmup_compile_seconds": cold_infer,
                 "warm_warmup_compile_seconds": warm_infer,
                 "cold_total_compile_seconds": scold["compile_seconds"],
                 "warm_total_compile_seconds": swarm["compile_seconds"],
                 "cold_ttfh_seconds": scold["ttfh_seconds"],
                 "warm_ttfh_seconds": swarm["ttfh_seconds"],
                 "ttfh_speedup": round(
                     scold["ttfh_seconds"]
                     / max(swarm["ttfh_seconds"], 1e-3), 1),
                 "cold_process_wall_s": round(scold_wall, 2),
                 "warm_process_wall_s": round(swarm_wall, 2),
                 "warmstart_adopted": swarm["warmstart_adopted"],
                 "artifact_bytes": os.path.getsize(art),
                 "replies_identical": identical,
                 "note": "cold boot compiles every bucket and exports "
                         "the warmstart artifact; warm boot adopts it "
                         "(ttfh = Engine construction through "
                         "warmup()); totals include the model-LOAD "
                         "step compile, which the artifact does not "
                         "target (enable PADDLE_TPU_COMPILE_CACHE to "
                         "kill that one too)"})
        except Exception as e:
            _emit_raw("coldstart_serving_warmup_compile_speedup", 0.0,
                      "x", 0.0, {"error": str(e)[:300]})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return train_ok and serve_ok


# ---------------------------------------------------------------------------
# Precision block (ISSUE 7): the mixed-precision + int8 hot paths.
# Train A/B — the SAME LeNet Program trained by the streaming driver
# under f32 vs mixed_bf16 (bf16 feeds end to end, so the hot path pays
# ZERO silent upcasts) with loss parity asserted. Serve A/B — the same
# saved model behind the bucketed Engine at f32 vs int8 (calibrated
# post-training quantization) with per-request p50/p99 and the reply
# accuracy delta. On TPU these are the native-width numbers the
# roadmap's per-chip-speed axis asks for; on CPU the block verifies
# both paths end to end (bf16/int8 emulation makes CPU speedups
# meaningless, so acceptance is parity + zero-upcast, not throughput).
# ---------------------------------------------------------------------------


# stated acceptance bounds (also asserted by the --smoke slow test):
# per-step |loss_mixed - loss_f32| <= 0.05 * max(1, |loss_f32|) with a
# final-loss relative delta <= 0.05; int8 replies within 0.05 absolute
# of f32 on the same bucket set (softmax outputs, so 0.05 is 5 points)
PRECISION_LOSS_REL_BOUND = 0.05
PRECISION_INT8_ABS_BOUND = 0.05


def bench_precision(mesh, n_chips, platform, on_tpu):
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import precision as pr
    from paddle_tpu.core.executor import _normalize_feed

    smoke = bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE")
                 or os.environ.get("PADDLE_TPU_COLDSTART_SMOKE"))
    ok_train = ok_serve = False

    # -- train A/B: f32 vs mixed_bf16 through run_stream ----------------
    try:
        import ml_dtypes

        rng = np.random.RandomState(0)
        bs = 8
        n_steps, window = (32, 8) if smoke else (128, 16)
        X = rng.rand(n_steps, bs, 1, 28, 28).astype("float32")
        Y = rng.randint(0, 10, (n_steps, bs, 1)).astype("int64")
        main, startup, loss = _build_lenet_program(pt)
        place = pt.TPUPlace() if on_tpu else pt.CPUPlace()
        exe = pt.Executor(place)

        def feeds_for(policy):
            # the input pipeline delivers the policy's width: bf16
            # feeds under mixed_bf16, proving the hot path never
            # upcasts them (the pre-PR executor astype'd every feed
            # to the declared f32 — core/executor.py _normalize_feed)
            if policy == "mixed_bf16":
                Xp = X.astype(ml_dtypes.bfloat16)
            else:
                Xp = X
            return [{"x": Xp[i], "y": Y[i]} for i in range(n_steps)]

        def phase(policy):
            pr.set_program_precision(main, policy)
            feeds = feeds_for(policy)
            # warm compiles on a throwaway scope
            with pt.scope_guard(pt.Scope()):
                exe.run(startup)
                for h in exe.run_stream(main, iter(feeds[:window + 1]),
                                        fetch_list=[loss], window=window):
                    h.result()
            with pt.scope_guard(pt.Scope()):
                exe.run(startup)
                losses = []
                t0 = time.perf_counter()
                for h in exe.run_stream(main, iter(feeds),
                                        fetch_list=[loss], window=window):
                    losses.extend(
                        float(v) for v in np.asarray(
                            h.result()[0], np.float32).ravel())
                dt = time.perf_counter() - t0
            return dt, losses

        # best-of-2 per policy: noisy-neighbor CPU must not decide the A/B
        f32_dt, f32_losses = min((phase("f32") for _ in range(2)),
                                 key=lambda r: r[0])
        bf16_dt, bf16_losses = min((phase("mixed_bf16")
                                    for _ in range(2)),
                                   key=lambda r: r[0])
        pr.set_program_precision(main, None)

        # zero-upcast probe: a bf16 feed under the mixed policy must
        # come back from feed normalization UNTOUCHED (same buffer, no
        # astype) — the acceptance criterion made checkable
        xb = jnp.asarray(X[0].astype(ml_dtypes.bfloat16))
        probe = _normalize_feed(main, {"x": xb},
                                pr.get_policy("mixed_bf16"))
        upcast_free = probe["x"] is xb and probe["x"].dtype == xb.dtype

        rel = [abs(a - b) / max(1.0, abs(b))
               for a, b in zip(bf16_losses, f32_losses)]
        max_rel = max(rel)
        final_rel = abs(bf16_losses[-1] - f32_losses[-1]) \
            / max(1.0, abs(f32_losses[-1]))
        f32_sps = bs * n_steps / f32_dt
        bf16_sps = bs * n_steps / bf16_dt
        speedup = bf16_sps / f32_sps
        ok_train = (max_rel <= PRECISION_LOSS_REL_BOUND
                    and final_rel <= PRECISION_LOSS_REL_BOUND
                    and upcast_free
                    and f32_losses[-1] < f32_losses[0]
                    and bf16_losses[-1] < bf16_losses[0])
        _emit_raw(
            "precision_bf16_train_samples_per_sec", bf16_sps,
            "samples/s", speedup,
            {"platform": platform, "batch_size": bs, "steps": n_steps,
             "window": window, "policy": "mixed_bf16",
             "f32_samples_per_sec": round(f32_sps, 2),
             "bf16_vs_f32_speedup": round(speedup, 3),
             "loss_rel_delta_max": round(max_rel, 5),
             "loss_rel_delta_final": round(final_rel, 5),
             "loss_rel_bound": PRECISION_LOSS_REL_BOUND,
             "final_loss_f32": round(f32_losses[-1], 5),
             "final_loss_bf16": round(bf16_losses[-1], 5),
             "bf16_feeds_upcast_free": bool(upcast_free),
             "note": "run_stream windowed driver, bf16 feeds end to "
                     "end under mixed_bf16 (zero per-step astype on "
                     "the hot path); CPU emulates bf16 so only TPU "
                     "speedups are meaningful"})
    except Exception as e:
        _emit_raw("precision_bf16_train_samples_per_sec", 0.0,
                  "samples/s", 0.0, {"error": str(e)[:300]})

    # -- serve A/B: f32 vs int8 through the bucketed Engine --------------
    try:
        import shutil
        import tempfile

        from paddle_tpu.serving import Engine, ServingConfig

        tmp = tempfile.mkdtemp(prefix="paddle_tpu_precision_")
        try:
            md = os.path.join(tmp, "model")
            mainm, startm = pt.Program(), pt.Program()
            with pt.framework.unique_name.guard(), \
                    pt.program_guard(mainm, startm):
                x = pt.layers.data(name="x", shape=[64], dtype="float32")
                h = pt.layers.fc(input=x, size=128, act="relu")
                predv = pt.layers.fc(input=h, size=16, act="softmax")
            exe2 = pt.Executor(pt.CPUPlace())
            with pt.scope_guard(pt.Scope()):
                exe2.run(startm)
                pt.io.save_inference_model(md, ["x"], [predv], exe2,
                                           main_program=mainm)
            rngs = np.random.RandomState(1)
            cal = [{"x": rngs.rand(4, 64).astype("float32")}
                   for _ in range(8)]
            buckets = (1, 2, 4)
            n_req = 40 if smoke else 200

            def build(precision):
                cfg = ServingConfig(
                    md, buckets=buckets, use_tpu=on_tpu,
                    precision=precision,
                    calibration=(lambda: iter(cal))
                    if precision == "int8" else None)
                eng = Engine(cfg)
                eng.warmup()
                return eng

            def measure(eng):
                reqs = [{"x": rngs.rand(2, 64).astype("float32")}
                        for _ in range(n_req)]
                eng.run_batch(reqs[0])  # page in the bucket
                lat = []
                outs = []
                for r in reqs:
                    t0 = time.perf_counter()
                    o = eng.run_batch(r)
                    lat.append(time.perf_counter() - t0)
                    outs.append(o)
                ms = np.asarray(lat) * 1000.0
                return (float(np.percentile(ms, 50)),
                        float(np.percentile(ms, 99)), reqs, outs)

            e32 = build("f32")
            p50_f32, p99_f32, reqs, outs_f32 = measure(e32)
            e8 = build("int8")
            # same request stream through int8: accuracy delta measured
            # on identical inputs, latency on its own pass
            lat = []
            max_abs = 0.0
            for r, o32 in zip(reqs, outs_f32):
                t0 = time.perf_counter()
                o8 = e8.run_batch(r)
                lat.append(time.perf_counter() - t0)
                for k in o32:
                    if k in o8:
                        max_abs = max(max_abs, float(np.abs(
                            np.asarray(o8[k], np.float32)
                            - np.asarray(o32[k], np.float32)).max()))
            ms = np.asarray(lat[1:] or lat) * 1000.0
            p50_i8 = float(np.percentile(ms, 50))
            p99_i8 = float(np.percentile(ms, 99))
            ok_serve = (max_abs <= PRECISION_INT8_ABS_BOUND
                        and e8.status()["precision"] == "int8"
                        and e8.accuracy_delta is not None)
            _emit_raw(
                "precision_int8_serving_p50_ms", p50_i8, "ms",
                p50_f32 / max(p50_i8, 1e-6),
                {"platform": platform, "buckets": list(buckets),
                 "requests": n_req,
                 "f32_p50_ms": round(p50_f32, 3),
                 "f32_p99_ms": round(p99_f32, 3),
                 "int8_p50_ms": round(p50_i8, 3),
                 "int8_p99_ms": round(p99_i8, 3),
                 "p50_speedup": round(p50_f32 / max(p50_i8, 1e-6), 3),
                 "accuracy_delta_max_abs": round(max_abs, 6),
                 "accuracy_bound": PRECISION_INT8_ABS_BOUND,
                 "engine_accuracy_delta": e8.accuracy_delta,
                 "note": "per-request Engine.run_batch on the shared "
                         "bucket set; int8 = calibrated post-training "
                         "quantization (quantized_* kernels, f32 "
                         "replies); CPU int8 matmul is emulated so "
                         "only TPU latency wins are meaningful"})
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    except Exception as e:
        _emit_raw("precision_int8_serving_p50_ms", 0.0, "ms", 0.0,
                  {"error": str(e)[:300]})
    return ok_train and ok_serve


def bench_resnet50(mesh, n_chips, platform, on_tpu):
    import dataclasses

    import optax

    from paddle_tpu.models import resnet
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    cfg = resnet.ResNetConfig.resnet50() if on_tpu \
        else resnet.ResNetConfig.tiny()
    hw = 224 if on_tpu else 32
    batch_sizes = [256, 128, 64, 32] if on_tpu else [16]

    def build_with(cfg):
        def build(bs):
            params, axes = resnet.init(jax.random.key(0), cfg)

            def loss_fn(p, b, r):
                # NHWC end-to-end: a real TPU input pipeline delivers
                # NHWC; the NCHW shim is reference-API parity only.
                return resnet.loss_fn(p, cfg, b, r, data_format="NHWC")

            init_state, step = make_train_step(
                loss_fn, optax.sgd(0.1, momentum=0.9), mesh, axes,
                strategy=TrainStrategy(shard_optimizer_states=False),
                has_aux=True)
            state = init_state(params)
            batch = resnet.make_batch(jax.random.key(1), cfg, bs, hw=hw,
                                      data_format="NHWC")
            return step, state, batch
        return build

    # A/B the pallas fused-1x1 path (byte-floor attack, PROFILE.md r5)
    # at a fixed shape; failure-isolated so a kernel/compile problem
    # costs only this detail field, never the headline metric.
    fused_ab = "not_measured"
    if on_tpu and mesh.devices.size == 1:
        from paddle_tpu.parallel import mesh_guard

        def _fused_ab():
            # inner function: its locals (params/moments/batch) die on
            # unwind even when _measure raises, so a failed A/B cannot
            # hold HBM through the headline ladder
            cfgf = dataclasses.replace(cfg, fused_1x1=True)
            with mesh_guard(mesh):
                step, state, batch = build_with(cfgf)(128)
                dt, _ = _measure(step, state, batch, 10)
            return {"step_ms_bs128": round(1000 * dt / 10, 2)}

        try:
            fused_ab = _fused_ab()
        except Exception as e:
            fused_ab = f"fail: {str(e)[:120]}"
        jax.clear_caches()

    return _run_ladder(
        "resnet50_train_samples_per_sec_per_chip" if on_tpu
        else "resnet_tiny_cpu_samples_per_sec",
        batch_sizes, build_with(cfg), cfg.flops_per_image(hw),
        20 if on_tpu else 3, n_chips, platform,
        {"image_hw": hw, "fused_1x1_ab": fused_ab}, mesh=mesh)


def bench_transformer_big(mesh, n_chips, platform, on_tpu):
    import optax

    from paddle_tpu.models import transformer
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    cfg = transformer.TransformerConfig.big() if on_tpu \
        else transformer.TransformerConfig.tiny()
    src_T = tgt_T = 128 if on_tpu else 16
    batch_sizes = [128, 64, 32, 16] if on_tpu else [8]

    def build(bs):
        params, axes = transformer.init(jax.random.key(0), cfg)

        def loss_fn(p, b, r):
            return transformer.nmt_loss(p, cfg, b, rng=r)

        init_state, step = make_train_step(
            loss_fn, optax.adam(1e-4), mesh, axes,
            strategy=TrainStrategy(shard_optimizer_states=True))
        state = init_state(params)
        batch = transformer.make_batch(jax.random.key(1), cfg, bs,
                                       src_T=src_T, tgt_T=tgt_T)
        return step, state, batch

    return _run_ladder(
        "transformer_big_nmt_train_samples_per_sec_per_chip" if on_tpu
        else "transformer_tiny_cpu_samples_per_sec",
        batch_sizes, build, cfg.train_flops_per_seq(src_T, tgt_T),
        20 if on_tpu else 3, n_chips, platform,
        {"src_len": src_T, "tgt_len": tgt_T,
         "tokens_per_sample": src_T + tgt_T}, mesh=mesh)


def bench_bert(mesh, n_chips, platform, on_tpu):
    import optax

    from paddle_tpu.models import bert
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    cfg = bert.BertConfig.base() if on_tpu else bert.BertConfig.tiny()
    seq_len = 128 if on_tpu else 64
    batch_sizes = [256, 512, 128, 64, 32] if on_tpu else [16]

    def build(bs):
        params, axes = bert.init(jax.random.key(0), cfg)

        def loss_fn(p, b, r):
            return bert.pretrain_loss(p, cfg, b, rng=r, deterministic=False)

        init_state, step = make_train_step(
            loss_fn, optax.adamw(1e-4), mesh, axes,
            strategy=TrainStrategy(shard_optimizer_states=True))
        state = init_state(params)
        batch = bert.make_batch(jax.random.key(1), cfg, batch_size=bs,
                                seq_len=seq_len)
        return step, state, batch

    # n_masked is a function of seq_len alone (make_batch masks a fixed
    # fraction) — read it off a tiny probe batch for the FLOPs model
    probe = bert.make_batch(jax.random.key(1), cfg, batch_size=2,
                            seq_len=seq_len)
    n_masked = probe["masked_positions"].shape[1]
    return _run_ladder(
        "bert_base_train_samples_per_sec_per_chip" if on_tpu
        else "bert_tiny_cpu_samples_per_sec",
        batch_sizes, build, cfg.train_flops_per_seq(seq_len, n_masked),
        20 if on_tpu else 3, n_chips, platform, {"seq_len": seq_len},
        mesh=mesh)


def bench_bert_long(mesh, n_chips, platform, on_tpu):
    """Long-sequence config (T=4096): measures the production attention
    path (auto gate = splash_attention with v5e-tuned blocks for
    T>=1024; PROFILE.md round 4) and A/Bs the XLA bf16-scores path at
    the same shape, making the gate decision reproducible from BENCH
    output."""
    if not on_tpu:
        return True  # flash path is TPU-only; CPU ladder covers tiny BERT
    import optax

    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    seq_len = 4096
    cfg = bert.BertConfig(max_len=seq_len, dropout=0.0)

    def build_with(mode):
        def build(bs):
            set_flags({"FLAGS_flash_attention": mode})
            params, axes = bert.init(jax.random.key(0), cfg)

            def loss_fn(p, b, r):
                return bert.pretrain_loss(p, cfg, b, rng=r,
                                          deterministic=True)

            init_state, step = make_train_step(
                loss_fn, optax.adamw(1e-4), mesh, axes,
                strategy=TrainStrategy(shard_optimizer_states=True))
            state = init_state(params)
            batch = bert.make_batch(jax.random.key(1), cfg, batch_size=bs,
                                    seq_len=seq_len)
            return step, state, batch
        return build

    probe = bert.make_batch(jax.random.key(1), cfg, batch_size=2,
                            seq_len=seq_len)
    n_masked = probe["masked_positions"].shape[1]
    flops = cfg.train_flops_per_seq(seq_len, n_masked)

    # A/B the XLA bf16-scores path at a fixed shape (bs=8): its per-step
    # time vs the production (splash) ladder below keeps the auto-gate
    # decision reproducible from BENCH output alone. Guarded like the
    # ladder (shard() constraints need the mesh) and dropped before the
    # ladder runs so its params/moments/batch don't hold HBM.
    from paddle_tpu.parallel import mesh_guard

    xla_detail = "not_measured"
    try:
        with mesh_guard(mesh):
            step, state, batch = build_with("off")(8)
            dt, _ = _measure(step, state, batch, 5)
        xla_detail = round(1000 * dt / 5, 2)
        del step, state, batch
    except Exception as e:
        xla_detail = f"fail: {str(e)[:120]}"
    jax.clear_caches()

    # what the auto gate selects at this mesh size: plain splash on one
    # chip; under multi-chip meshes the r5 compositions ride instead
    # (shard_map splash when seq is unsharded, ring-splash under sp —
    # attention.py _multichip_splash_route)
    attn_label = ("splash(auto gate)" if mesh.devices.size == 1
                  else "splash_multichip(auto gate: shardmap/ring)")
    ok = _run_ladder(
        "bert_long_seq4096_train_samples_per_sec_per_chip",
        [8, 4, 2, 1], build_with("auto"), flops, 5, n_chips,
        platform,
        {"seq_len": seq_len, "attention": attn_label,
         "xla_bf16_step_ms_bs8": xla_detail}, mesh=mesh)
    set_flags({"FLAGS_flash_attention": "auto"})
    return ok


# ---------------------------------------------------------------------------
# Orchestration: a chip belongs to one process at a time, and the
# measurement children each need it. The parent process below therefore
# NEVER initializes a jax backend (tests/test_chip_smoke.py holds it to
# that): it probes the backend in a bounded subprocess, then runs each
# metric in its own subprocess with its own timeout, forwarding the JSON
# lines. A hang or crash in one metric costs exactly that metric (a
# structured {"metric":..., "error":...} line), never the file.
# ---------------------------------------------------------------------------

# (name, tpu_metric, cpu_metric, timeout_s); bert prints LAST (flagship).
BENCHES = [
    ("lenet", "lenet_mnist_program_smoke_samples_per_sec",
     "lenet_mnist_program_smoke_samples_per_sec", 600),
    ("pipeline", "pipeline_stream_samples_per_sec",
     "pipeline_stream_samples_per_sec", 600),
    ("coldstart", "coldstart_restart_compile_speedup",
     "coldstart_restart_compile_speedup", 900),
    ("precision", "precision_bf16_train_samples_per_sec",
     "precision_bf16_train_samples_per_sec", 900),
    ("resnet50", "resnet50_train_samples_per_sec_per_chip",
     "resnet_tiny_cpu_samples_per_sec", 900),
    ("transformer", "transformer_big_nmt_train_samples_per_sec_per_chip",
     "transformer_tiny_cpu_samples_per_sec", 900),
    ("bert_long", "bert_long_seq4096_train_samples_per_sec_per_chip",
     None, 900),  # CPU ladder covers tiny BERT; long-seq is TPU-only
    ("bert", "bert_base_train_samples_per_sec_per_chip",
     "bert_tiny_cpu_samples_per_sec", 900),
]
_BENCH_FNS = {
    "lenet": bench_lenet_smoke, "pipeline": bench_pipeline,
    "precision": bench_precision, "resnet50": bench_resnet50,
    "transformer": bench_transformer_big, "bert_long": bench_bert_long,
    "bert": bench_bert,
}


def run_one(name):
    """Child mode: run one bench in-process (the only mode that touches jax
    backends)."""
    from paddle_tpu.core.compile_cache import place_jax_cache

    place_jax_cache()
    if os.environ.get("PADDLE_TPU_BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")  # explicit CPU run
    if name == "coldstart":
        # subprocess-only block: initializing a backend HERE would hold
        # the TPU its measurement children need to boot cold — the env
        # block takes the parent's probe verdict instead of asking jax
        _init_bench_env()
        return 0 if bench_coldstart(
            smoke=bool(os.environ.get("PADDLE_TPU_COLDSTART_SMOKE"))) \
            else 1
    from paddle_tpu.parallel import MeshConfig, make_mesh

    platform = jax.devices()[0].platform
    _init_bench_env(platform=platform)
    on_tpu = platform == "tpu"
    mesh = make_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1]) \
        if len(jax.devices()) == 1 else make_mesh(MeshConfig(dp=-1))
    ok = _BENCH_FNS[name](mesh, mesh.devices.size, platform, on_tpu)
    return 0 if ok else 1


def _run_bounded(argv, timeout_s, env=None):
    """subprocess.run with HARD bounds: the child runs in its own session
    so a timeout kills the whole process group (a backend helper
    grandchild inheriting the pipes would otherwise hold them open and
    block subprocess.run's post-kill drain forever), and the post-kill
    drain itself is bounded. Returns (rc, stdout, stderr); rc is None on
    timeout."""
    import signal
    import subprocess

    try:
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True, env=env,
                                start_new_session=True)
    except OSError as e:
        # spawn failure (fork EAGAIN/ENOMEM on an exhausted host) is the
        # same class of event as a wedged backend: report it structured,
        # don't crash the orchestrator
        return None, "", f"spawn failed: {e}"
    try:
        out, err = proc.communicate(timeout=timeout_s)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            proc.kill()
        try:
            out, err = proc.communicate(timeout=15)
        except (subprocess.TimeoutExpired, OSError):
            out = err = ""
            for stream in (proc.stdout, proc.stderr):
                try:
                    if stream:
                        stream.close()
                except OSError:
                    pass
        return None, out, err


def _probe_backend(timeout_s):
    """Probe the default platform in a throwaway subprocess (the parent
    must not touch the backend itself, and a backend init that hangs is
    bounded only by a killable process). Returns the platform string or
    None."""
    code = ("import jax, json; d = jax.devices(); import jax.numpy as jnp;"
            " v = float(jnp.ones((128, 128)).sum());"
            " print(json.dumps({'platform': d[0].platform, 'ok': v == 16384.0}))")
    rc, out, _ = _run_bounded([sys.executable, "-c", code], timeout_s)
    if rc == 0:
        try:
            info = json.loads(out.strip().splitlines()[-1])
            if info.get("ok"):
                return info["platform"]
        except (ValueError, IndexError):
            pass
    return None


def _emit_error(metric, error):
    print(json.dumps({"metric": metric, "value": 0.0,
                      "unit": "samples/s/chip", "vs_baseline": 0.0,
                      "env": dict(_BENCH_ENV),
                      "error": error[:300]}), flush=True)


def _forward_child_output(stdout, stderr):
    """Pass the child's JSON metric lines through; anything else (jax
    warnings, tracebacks) goes to stderr. Returns emitted metric names."""
    emitted = []
    for line in (stdout or "").splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            rec = None
        if not isinstance(rec, dict) or "metric" not in rec:
            print(line, file=sys.stderr)
            continue
        print(line, flush=True)
        emitted.append(rec["metric"])
    if stderr:
        sys.stderr.write(stderr[-4000:])
    return emitted


def main():
    from paddle_tpu.core.tpu_lock import tpu_singleflight

    deadline = time.monotonic() + float(
        os.environ.get("PADDLE_TPU_BENCH_DEADLINE_S", "3000"))
    with tpu_singleflight(timeout=600.0):
        if os.environ.get("PADDLE_TPU_BENCH_FORCE_CPU"):
            platform = "cpu"  # explicit CPU run: skip the TPU probe
            probed = False
        else:
            platform = _probe_backend(240) or (time.sleep(20) or
                                               _probe_backend(180))
            probed = True
        env = dict(os.environ)
        # probe verdict → env block on every line, parent and children
        # (an explicit CPU run never probed, so reachability is unknown
        # there — None — and nothing is a "fallback")
        if probed:
            env["PADDLE_TPU_BENCH_TPU_REACHABLE"] = \
                "1" if platform == "tpu" else "0"
        if platform is None:
            env["PADDLE_TPU_BENCH_FALLBACK_REASON"] = (
                "TPU backend probe failed/hung (bounded at 240s+180s); "
                "falling back to CPU")
        env["PADDLE_TPU_BENCH_PLATFORM"] = platform or "cpu"
        os.environ.update({k: env[k] for k in
                           ("PADDLE_TPU_BENCH_TPU_REACHABLE",
                            "PADDLE_TPU_BENCH_FALLBACK_REASON",
                            "PADDLE_TPU_BENCH_PLATFORM") if k in env})
        _init_bench_env(platform=platform or "cpu")
        if platform is None:
            # Wedged/absent default backend: record a structured failure
            # per TPU metric, then still exercise the ladder on CPU so
            # the bench machinery itself stays verified. Metrics whose
            # name is platform-independent (lenet smoke) are skipped
            # here — the CPU fallback emits the real line under the
            # same name and a 0.0 error twin would contradict it.
            for _, tpu_metric, cpu_metric, _ in BENCHES:
                if tpu_metric != cpu_metric:
                    _emit_error(tpu_metric,
                                "TPU backend probe failed/hung (bounded "
                                "at 240s+180s); falling back to CPU")
            env["PADDLE_TPU_BENCH_FORCE_CPU"] = "1"
        on_tpu = platform == "tpu"

        all_ok = platform is not None
        here = os.path.abspath(__file__)
        for name, tpu_metric, cpu_metric, tmo in BENCHES:
            expected = tpu_metric if on_tpu else cpu_metric
            budget = min(tmo, deadline - time.monotonic())
            if budget < 60:
                if expected:
                    _emit_error(expected, "bench deadline exhausted before "
                                "this metric started")
                all_ok = False
                continue
            rc, out, err = _run_bounded(
                [sys.executable, here, "--one", name], budget, env=env)
            emitted = _forward_child_output(out, err)
            if rc is None:
                if expected and expected not in emitted:
                    reason = (err if err.startswith("spawn failed")
                              else f"bench subprocess timed out after "
                                   f"{budget:.0f}s (process group killed)")
                    _emit_error(expected, reason)
                all_ok = False
            elif rc != 0:
                all_ok = False
                if expected and expected not in emitted:
                    _emit_error(expected,
                                f"bench subprocess rc={rc} exited "
                                "without emitting this metric")
            elif expected and expected not in emitted:
                _emit_error(expected,
                            "bench subprocess exited rc=0 without "
                            "emitting this metric")
                all_ok = False
        # BASELINE config 5 (ResNet-50 data-parallel on v5e-8) needs 8
        # real chips; its sharded step is validated by
        # __graft_entry__.dryrun and the ParallelExecutor parity tests.
        return 0 if all_ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--coldstart-child":
        sys.exit(_coldstart_child(sys.argv[2:]))
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        if "--smoke" in sys.argv[3:]:
            # coldstart's measurement children inherit this via env;
            # the precision block reads the generic flag
            os.environ["PADDLE_TPU_COLDSTART_SMOKE"] = "1"
            os.environ["PADDLE_TPU_BENCH_SMOKE"] = "1"
        sys.exit(run_one(sys.argv[2]))
    sys.exit(main())
