"""Runner of every traffic file of kind `serve`: the program's
`DecodeEngine` behind its `serving.Server` in this process (which holds the
chip), the load generator in a child that never imports JAX, a window on
the clients' clock, and the records the per-layer readers read. A traced
run turns the PROGRAM's own recording on (its spans, step and request
records: PERF.md section 3) and reads the layer scopes of its device
programs; an untraced run records nothing and wraps nothing. The weights
are on the device once at a time: in the served precision while the engine
lives, in float32 for the reference only after engine, server and pools
are gone. Nothing here names a configuration or a cell."""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time
from typing import Dict

from ..harness import device, manifest, program_trace, trace_reduce
from ..harness import traffic as traffic_mod, window

LOADGEN = os.path.join(manifest.BENCH_DIR, "harness", "loadgen.py")
# the engine loop's own spans (`paddle_tpu.observability.tracing`, on while
# a traced run records), innermost last where they nest: a device idle gap
# goes to the innermost of them that covers it
LOOP_SPANS = ("decode.turn", "decode.admit", "decode.grow", "decode.prefill",
              "decode.prefill.wait", "decode.dispatch", "decode.resolve",
              "decode.resolve.wait")
TAIL_S = 2.0    # load past the window: a request due at its end can still
                # show its first token
TRACE_S = 4.0   # traced sub-window, under continuing load
# `serve.precision` of a configuration file -> the dtype the weights are
# made in (the engine serves these two and casts nothing that already fits)
SERVED_DTYPE = {"bf16": "bfloat16", "f32": "float32"}


def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def _counters() -> Dict:
    from paddle_tpu.serving import decode as d

    return {"occupancy": d.OCCUPANCY.stats(),
            "compile_requests": device.COUNTS["compile_requests"]}


def _warm_request(port: int, ids, max_new: int) -> None:
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/generate",
                     body=json.dumps({"ids": ids,
                                      "max_new_tokens": max_new}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200 or b'"done": true' not in body:
            raise RuntimeError(f"warm-up request failed: HTTP "
                               f"{resp.status} {body[-200:]!r}")
    finally:
        conn.close()


def _write_program(program: Dict, w0: float, out_dir: str) -> None:
    """The program's recording of the window, times from its opening."""
    with open(os.path.join(out_dir, "program_spans.jsonl"), "w") as f:
        for name, a, b, tid, facts in program["spans"]:
            f.write(json.dumps({"name": name, "t0_s": a - w0,
                                "seconds": b - a, "tid": tid, **facts},
                               default=str) + "\n")
    with open(os.path.join(out_dir, "engine_steps.jsonl"), "w") as f:
        for s in program["steps"]:
            f.write(json.dumps(dict(s, t=s["t"] - w0)) + "\n")
    stamps = ("arrival", "t_submit", "enqueued_at", "admitted_at",
              "t_first", "t_finish")
    with open(os.path.join(out_dir, "engine_requests.jsonl"), "w") as f:
        for r in program["requests"]:
            f.write(json.dumps({k: (v - w0 if k in stamps and v else v)
                                for k, v in r.items()}) + "\n")


def _served_plans(engine) -> Dict:
    """The compiler's plan of every served program the engine compiled."""
    plans = {}
    for kind, table in (("decode", engine._decode),
                        ("prefill", engine._prefill)):
        for size, disp in table.items():
            aot = getattr(disp, "_aot", None)
            if aot is not None:
                plans[f"{kind}@{size}"] = device.planned_bytes(aot)
    return plans


def run(cell: Dict, args, out_dir: str, allow_cpu: bool = False) -> Dict:
    import jax
    import numpy as np

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import Server, ServingConfig
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    dev, peaks = device.start(cell["chips"], allow_cpu)
    config = cell["config_file"]
    traffic = dict(cell["traffic_file"])
    if getattr(args, "rate", None):
        traffic["rate_per_s"] = float(args.rate)  # the knee sweep only
    family = manifest.plugin("families", config["family"])
    model, serve = config["model"], config["serve"]
    cfg = family.make_config(model)
    devices = jax.devices()[:cell["chips"]]
    resident_at_start = device.resident_bytes(devices)

    if args.trace:
        # the program's spans and records, from before the engine exists
        # (it keys its phase grid's compile cache on metadata itself, so
        # the scopes a traced run reads are never stale)
        tracing.start_recording()
    slots = max(serve["decode_slots"])
    pool_tokens = slots * serve["kv_context_per_slot"]
    engine = server = child = None
    try:
        # the weights in the precision they are served in, cast inside the
        # one program that makes them: float32 is never whole on the device
        params, _ = family.init(cfg, args.seed,
                                dtype=SERVED_DTYPE[serve["precision"]])
        weight_bytes = sum(int(v.nbytes)
                           for v in jax.tree_util.tree_leaves(params))
        engine = DecodeEngine(params, cfg, DecodeConfig(
            block_size=serve["block_size"],
            num_blocks=slots * (serve["kv_context_per_slot"]
                                // serve["block_size"]) + 1,
            decode_slots=tuple(serve["decode_slots"]),
            prefill_buckets=tuple(traffic["prefill_buckets"]),
            max_queue=int(serve["max_queue"]),
            precision=serve["precision"], eos_id=serve["eos_id"]))
        del params      # the engine holds them now: one copy
        resident_after_build = device.resident_bytes(devices)
        engine.warmup()
        server = Server(ServingConfig(), decode=engine)
        port = server.start(0)
        # one request per prefill bucket through the whole served path
        for b in engine.prefill_buckets:
            _warm_request(port, traffic_mod.prompt_ids(
                args.seed, 10 ** 6 + b, min(b, model["max_len"] - 4),
                model["vocab_size"]), 3)

        t0 = time.monotonic() + 1.0
        w0 = t0 + float(traffic["lead_s"])
        w1 = w0 + float(args.seconds)
        # a traced run keeps the load up while the profiler starts (some
        # seconds the first time) and takes its trace
        t_stop = w1 + TAIL_S + (TRACE_S + 6.0 if args.trace else 0.0)
        job = {"port": port, "seed": args.seed, "traffic": traffic,
               "vocab_size": model["vocab_size"], "t0": t0,
               "t_stop": t_stop, "timeout_s": 300,
               "out": os.path.join(out_dir, "requests.jsonl")}
        job_path = os.path.join(out_dir, "loadgen_job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        child = subprocess.Popen([sys.executable, LOADGEN, job_path])

        _sleep_until(w0)
        setup_s = time.monotonic() - args.t_start
        c0 = dict(_counters(), load=engine.load())
        _sleep_until(w1)
        c1 = dict(_counters(), load=engine.load())
        kv_close = engine.status()["kv"]

        if args.trace:
            trace_dir = os.path.join(out_dir, "trace")
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profile_options())
            ta = time.monotonic()
            time.sleep(TRACE_S)
            tb = time.monotonic()
            jax.profiler.stop_trace()
        child.wait(timeout=60)
        status = engine.status()
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if server is not None:
            server.stop()
        if engine is not None:
            engine.stop()
        if args.trace:
            tracing.stop_recording()

    # memory, while what served the window is still there: the compiler's
    # plan of the largest served program plus what is resident
    plans = _served_plans(engine)
    resident = device.resident_bytes(devices)
    planned_total = resident + max(
        (p.get("temp", 0) + max(0, p.get("output", 0) - p.get("alias", 0))
         for p in plans.values()), default=0)
    dev["memory_peak_bytes"] = int(max(
        planned_total, device.runtime_peak_bytes(devices)))
    # ... and gone before the reference's float32 parameters are made
    del engine, server
    gc.collect()
    resident_dropped = device.resident_bytes(devices)

    trace = program = scopes = None
    checks: Dict = {}
    if args.trace:
        program = program_trace.collect(w0, w1)
        if program is None or not any(
                s[0] == "decode.turn" for s in program["spans"]):
            raise RuntimeError("the program recorded no decode.turn span "
                               "in the window: its recording is off, or "
                               "the engine loop lost its spans")
        _write_program(program, w0, out_dir)
        checks["recording"] = {
            "spans_per_s": len(program["spans"]) / (w1 - w0),
            "records_per_s": (len(program["steps"])
                              + len(program["requests"])) / (w1 - w0),
            "dropped_spans": program["dropped"]}
        xplane = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.reduce_loaded(
            trace_reduce.load_xplane(xplane, LOOP_SPANS), "engine_other",
            innermost=True)
        scopes = program_trace.reduce_scopes(xplane)
        with open(os.path.join(out_dir, "device_scopes.json"), "w") as f:
            json.dump(scopes, f, indent=1)
        if not scopes.get("scoped_ops"):
            raise RuntimeError(
                "no device op of the trace carries a layer scope: the "
                "executables predate the scopes (a compile cache keyed "
                "without metadata?)")
        # least bytes of a decode step at the traced load, from the
        # program's step records of the traced sub-window
        live = [s["live_tokens"] for s in program_trace.collect(
            ta, tb)["steps"] if s["kind"] == "decode"]
        if live:
            trace["live_tokens_mean"] = float(np.mean(live))
            trace["decode_min_bytes"] = family.decode_step_min_bytes(
                model, trace["live_tokens_mean"])

    with open(job["out"]) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    win = window.stream_window(requests, w0, w1)

    # correct: every finished stream has the tokens it asked for, a seeded
    # sample of four agrees with the float32 reference, nothing compiled
    finished = [r for r in requests if r["done"]]
    short = [r["idx"] for r in finished
             if len(r["tokens"]) != r["asked"]
             or not all(0 <= t < model["vocab_size"] for t in r["tokens"])]
    n_check, n_tok = 4, 16
    pool = sorted((r for r in finished if len(r["tokens"]) >= n_tok),
                  key=lambda r: r["idx"])
    rng = np.random.default_rng([args.seed, 0xC0FFEE])
    sample = [pool[i] for i in sorted(rng.choice(
        len(pool), size=min(n_check, len(pool)), replace=False))]
    gap = exact = resident_at_reference = None
    if len(sample) == n_check:
        params, _ = family.init(cfg, args.seed)     # float32, the only set
        resident_at_reference = device.resident_bytes(devices)
        gap, exact = family.reference_gaps(
            params, model,
            [traffic_mod.prompt_ids(args.seed, r["idx"], r["prompt_len"],
                                    model["vocab_size"]) for r in sample],
            [r["tokens"][:n_tok] for r in sample], model["max_len"])
        del params
    window_compiles = c1["compile_requests"] - c0["compile_requests"]
    checks.update({
        "finished": len(finished), "short_streams": short[:8],
        "sampled": [r["idx"] for r in sample],
        "ref_max_logit_gap": gap, "ref_exact_tokens": exact,
        "ref_tokens": n_check * n_tok,
        "logit_gap_tol": config["logit_gap_tol"],
        "compiles_in_window": window_compiles,
        "engine_requests": status["requests"],
        # (waiting, active) at the window's edges: a queue that
        # grows through the window means the rate is past the knee
        "load_open": c0["load"], "load_close": c1["load"],
        "ttft_p95_ms": None if not win["ttft_s"]
        else 1000.0 * window.percentile(win["ttft_s"], 95),
        "requests_in_window": win["attempted"],
        "tokens_in_window": win["tokens"]})
    correct = (gap is not None and gap <= config["logit_gap_tol"]
               and not short and window_compiles == 0)

    checks["memory"] = {
        "resident_bytes": resident, "plans": plans,
        "weight_bytes": weight_bytes, "kv_pool_bytes": kv_close["pool_bytes"],
        "resident_at_start": resident_at_start,
        "resident_after_build": resident_after_build,
        "resident_dropped": resident_dropped,
        "resident_at_reference": resident_at_reference,
        "kv_pool_tokens": pool_tokens,
        "kv_bytes_per_token": family.kv_bytes_per_token(model),
        # one reading at the window's close; the mean over the window is
        # the traced run's `kv_block_used_share`
        "kv_live_tokens_close": kv_close["live_tokens"],
        "kv_blocks_used_close": kv_close["blocks_used"]}

    records = {
        "kind": "serve", "chips": cell["chips"], "peaks": peaks,
        "model": model, "kv_pool_tokens": pool_tokens,
        "window_s": w1 - w0, "window": win,
        "counters": {"open": c0, "close": c1},
        "planned_bytes": planned_total, "trace": trace,
        "program": program, "scopes": scopes,
    }
    ms = 1000.0
    end_to_end = {
        "serve_tokens_per_s": win["tokens_per_s"],
        "itl_p95_ms": None if not win["gaps_s"]
        else ms * window.percentile(win["gaps_s"], 95),
        "setup_s": setup_s,
    }
    return {"correct": bool(correct), "attempted": win["attempted"],
            "failed": win["failed"], "end_to_end": end_to_end,
            "records": records, "device": dev, "checks": checks}
