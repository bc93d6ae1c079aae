"""Runner of every traffic file of kind `serve`: the program's
`DecodeEngine` behind its `serving.Server` in this process (which holds the
chip), the load generator in a child that never imports JAX, a window on
the clients' clock, and the records the per-layer readers read. Nothing
here names a configuration or a cell."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

from ..harness import device, manifest, trace_reduce, traffic as traffic_mod
from ..harness import window

LOADGEN = os.path.join(manifest.BENCH_DIR, "harness", "loadgen.py")
HOST_SPANS = ("prefill", "engine_dispatch", "engine_resolve")
TAIL_S = 2.0    # load past the window: a request due at its end can still
                # show its first token
TRACE_S = 4.0   # traced sub-window, under continuing load


def instrument(engine, spans: List) -> None:
    """Harness-side spans around the engine's calls into its layers: the
    prefill entry, the decode dispatch and the resolve of a step's tokens.
    Each is a (name, t0, t1, facts) row on CLOCK_MONOTONIC and, while a
    trace is taken, a TraceAnnotation on the profiler's clock."""
    import jax

    ann = jax.profiler.TraceAnnotation
    missing = [n for n in ("_prefill_one", "_dispatch", "_resolve")
               if not callable(getattr(engine, n, None))]
    if missing:  # a rename in serving/decode.py must not go unseen
        raise RuntimeError(f"DecodeEngine has no {missing}: the harness "
                           "spans of benchmarks/kinds/serve.py wrap them")
    prefill_one, dispatch, resolve = (
        engine._prefill_one, engine._dispatch, engine._resolve)

    def traced_prefill(req):
        t0 = time.monotonic()
        facts = {"queue_wait_s": t0 - req.enqueued_at,
                 "prompt_len": len(req.prompt)}
        with ann("prefill"):
            out = prefill_one(req)
        spans.append(("prefill", t0, time.monotonic(), facts))
        return out

    def traced_dispatch(ids_arg, C):
        t0 = time.monotonic()
        with ann("engine_dispatch"):
            out = dispatch(ids_arg, C)
        live = [r for r in out.slots if r is not None]
        spans.append(("engine_dispatch", t0, time.monotonic(),
                      {"slots": C, "live": len(live),
                       "live_tokens": sum(r.pos for r in live)}))
        return out

    def traced_resolve(pending):
        t0 = time.monotonic()
        with ann("engine_resolve"):
            out = resolve(pending)
        spans.append(("engine_resolve", t0, time.monotonic(), {}))
        return out

    engine._prefill_one = traced_prefill
    engine._dispatch = traced_dispatch
    engine._resolve = traced_resolve


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


def run(cell: Dict, args, out_dir: str, allow_cpu: bool = False) -> Dict:
    import jax
    import numpy as np

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

    params, _ = family.init(cfg, args.seed)
    slots = max(serve["decode_slots"])
    pool_tokens = slots * serve["kv_context_per_slot"]
    engine = DecodeEngine(params, cfg, DecodeConfig(
        block_size=serve["block_size"],
        num_blocks=slots * (serve["kv_context_per_slot"]
                            // serve["block_size"]) + 1,
        decode_slots=tuple(serve["decode_slots"]),
        prefill_buckets=tuple(traffic["prefill_buckets"]),
        max_queue=int(serve["max_queue"]), precision=serve["precision"],
        eos_id=serve["eos_id"]))
    # the engine serves its own cast of the weights: the float32 ones are
    # made again from the seed for the reference, after the window, so that
    # the chip holds what a deployment holds while it is measured
    del params
    engine.warmup()
    spans: List = []
    instrument(engine, spans)
    server = Server(ServingConfig(), decode=engine)
    port = server.start(0)
    child = None
    try:
        # one request per prefill bucket through the whole served path
        for b in engine.prefill_buckets:
            _warm_request(port, traffic_mod.prompt_ids(
                args.seed, 10 ** 6 + b, min(b, model["max_len"] - 4),
                model["vocab_size"]), 3)
        del spans[:]

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

        trace = None
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
        server.stop()
        engine.stop()

    if args.trace:
        trace = trace_reduce.reduce_dir(trace_dir, HOST_SPANS,
                                        "engine_other")
        live = [s[3]["live_tokens"] for s in spans
                if s[0] == "engine_dispatch" and ta <= s[1] < tb]
        if live:  # least bytes of a decode step at the traced load
            trace["live_tokens_mean"] = float(np.mean(live))
            trace["decode_min_bytes"] = family.decode_step_min_bytes(
                model, trace["live_tokens_mean"])

    in_win = [s for s in spans if w0 <= s[1] < w1]
    unseen = [n for n in HOST_SPANS if not any(s[0] == n for s in in_win)]
    if unseen:  # wrapped, but the engine's loop no longer calls them
        raise RuntimeError(f"no {unseen} span in the window: the engine "
                           "loop has changed under the harness's spans")

    # memory, before the reference's float32 parameters are made: the
    # compiler's plan of the largest served program plus what is resident
    plans = {}
    for kind, table in (("decode", engine._decode),
                        ("prefill", engine._prefill)):
        for size, disp in table.items():
            aot = getattr(disp, "_aot", None)
            if aot is not None:
                plans[f"{kind}@{size}"] = device.planned_bytes(aot)
    resident = device.resident_bytes(devices)
    planned_total = resident + max(
        (p.get("temp", 0) + max(0, p.get("output", 0) - p.get("alias", 0))
         for p in plans.values()), default=0)
    dev["memory_peak_bytes"] = int(max(
        planned_total, device.runtime_peak_bytes(devices)))

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
    gap = exact = None
    if len(sample) == n_check:
        params, _ = family.init(cfg, args.seed)
        gap, exact = family.reference_gaps(
            params, model,
            [traffic_mod.prompt_ids(args.seed, r["idx"], r["prompt_len"],
                                    model["vocab_size"]) for r in sample],
            [r["tokens"][:n_tok] for r in sample], model["max_len"])
    window_compiles = c1["compile_requests"] - c0["compile_requests"]
    checks = {"finished": len(finished), "short_streams": short[:8],
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
              "tokens_in_window": win["tokens"]}
    correct = (gap is not None and gap <= config["logit_gap_tol"]
               and not short and window_compiles == 0)

    live = [s[3]["live_tokens"] for s in in_win if s[0] == "engine_dispatch"]
    kv_token_bytes = family.kv_bytes_per_token(model)
    checks["memory"] = {
        "resident_bytes": resident, "plans": plans,
        "kv_pool_tokens": pool_tokens, "kv_bytes_per_token": kv_token_bytes,
        "kv_live_tokens_mean": float(np.mean(live)),
        "kv_live_tokens_max": int(max(live))}

    records = {
        "kind": "serve", "chips": cell["chips"], "peaks": peaks,
        "model": model, "kv_pool_tokens": pool_tokens,
        "window_s": w1 - w0, "window": win, "spans": in_win,
        "counters": {"open": c0, "close": c1},
        "planned_bytes": planned_total, "trace": trace,
    }
    with open(os.path.join(out_dir, "engine_spans.jsonl"), "w") as f:
        for name, a, b, facts in in_win:
            f.write(json.dumps({"name": name, "t0_s": a - w0,
                                "seconds": b - a, **facts}) + "\n")
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
