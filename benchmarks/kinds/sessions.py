"""Runner of every traffic file of kind `sessions`: a serve cell whose window
holds NO admission. Every session is one request of a closed loop, sent at
`t0`, prefilled while the lead runs (set-up), and judged while it decodes:
the window sees decode steps over long caches and nothing else. A whole
prefill of a long prompt inside a window is a large quantum whose count
swings from run to run (PERF.md section 6, PR 42: two `model_config` PRs
were refused for it); here the prefill's cost shows in `setup_s` and
`sessions_ready_s` alone, and nothing in the window sees it.

The traffic file is an ordinary closed loop for `harness/loadgen.py` and
`harness/traffic.py` (`clients` = `table_size` = the number of sessions,
which is at most the configuration's slots; `output_len` so long that no
session ends before the load stops: every stream is `cut`, not failed), with
TWO keys of this kind's own:

    context_per_slot   the cell's `serve.kv_context_per_slot` and
                       `model.max_len`: the longest prompt + `output_len`.
                       Allowed only where the configuration lists
                       `max_position_embeddings` in `reduced` (a deployment's
                       maximum model length, not a width) and states the
                       published value there (`reduced_why`: "<published> ->
                       <cut>"), which it may not pass. Slots, block size,
                       precision and state rows stay the configuration's.
    weights_seed       the seed of the weights, the program's and the
                       reference's alike, whatever `--seed` is: `--seed`
                       draws the prompts and deals the lengths. A sparse
                       model's random router is as uneven as its draw makes
                       it, so a step reads more or fewer experts' bytes from
                       draw to draw: with the weights from `--seed` the
                       proving cell's rate spread by 1.2% over six seeds
                       while two runs of ONE seed agreed to 0.04%, and a
                       seed is there to deal the same work in another
                       order, not to change it (PERF.md section 6, PR 42).

What a run checks besides what `kinds/serve.py` checks (`correct` is false
where one fails; `checks.compared` has each number beside its limit):

    sessions_ready     `requests.jsonl` holds exactly one record a session,
                       each sent before the window with its first token
                       before the window: else the lead was too short
    window_admissions  0: no request sent from the window's opening on, no
                       stream `done` (a session that ended was sent again by
                       its client), the engine's finished-request counts
                       equal at both edges with every session active, and in
                       a traced run no prefill step record and no
                       `decode.prefill` span inside the window
    ref_max_logit_gap  a seeded sample of four sessions, the longest among
                       them, each with 16 tokens received INSIDE the window:
                       the float32 reference is teacher-forced over the
                       session's prompt plus the tokens it generated before
                       the window and judges the first 16 in-window tokens,
                       so what is compared is what the timed path produced at
                       the timed context, through the cache and the state
                       rows, not the first tokens after a prefill

`attempted` is the number of sessions; `failed` counts a session that
errored, ended before the load stopped, or received no token inside the
window. The engine, server, load generator, recording, trace and memory
accounting are `kinds/serve.py`'s, imported where they are functions; the
order of a run is written a second time here (PERF.md section 7 names the
debt for the `benchmark` PR that next edits `serve.py`)."""

from __future__ import annotations

import copy
import gc
import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from ..harness import device, manifest, program_trace, trace_reduce
from ..harness import traffic as traffic_mod, window
from .serve import (LOADGEN, LOOP_SPANS, SERVED_DTYPE, TAIL_S, TRACE_S,
                    _counters, _served_plans, _sleep_until, _warm_request,
                    _write_program)

N_CHECK = 4         # sessions the reference judges
N_TOKENS = 16       # in-window tokens of each that it judges
SILENCE_S = 0.1     # a gap between token arrivals that counts as a silence
# `kind` of the step records of a prompt's programs (whole and chunked)
ADMISSION_STEPS = ("prefill", "chunk")
_PUBLISHED = re.compile(r"^\s*(\d+)\s*->")


def _longest(spec: Dict) -> int:
    return int(spec.get("hi", spec.get("value")))


def with_context(config: Dict, traffic: Dict) -> Dict:
    """The configuration with the traffic's `context_per_slot` as its
    `serve.kv_context_per_slot` and `model.max_len`; ValueError where the
    configuration does not allow it."""
    context = int(traffic["context_per_slot"])
    key = "max_position_embeddings"
    stated = _PUBLISHED.match(str((config.get("reduced_why") or {})
                                  .get(key, "")))
    if key not in (config.get("reduced") or []) or not stated:
        raise ValueError(
            f"`context_per_slot` needs a configuration that lists {key} in "
            f"`reduced` and states '<published> -> <cut>' under "
            f"`reduced_why`: {config.get('name')!r} does not")
    if not 0 < context <= int(stated.group(1)):
        raise ValueError(
            f"`context_per_slot` {context} is over the published {key} "
            f"{stated.group(1)} of {config.get('name')!r}")
    slots = max(config["serve"]["decode_slots"])
    if int(traffic["clients"]) != int(traffic["table_size"]) \
            or int(traffic["clients"]) > slots:
        raise ValueError(
            "a `sessions` mix has `clients` = `table_size` sessions, at "
            f"most the configuration's {slots} slots")
    if _longest(traffic["prompt_len"]) + _longest(traffic["output_len"]) \
            > context:
        raise ValueError("the longest prompt + the longest output is over "
                         f"`context_per_slot` {context}")
    config = copy.deepcopy(config)
    config["serve"]["kv_context_per_slot"] = context
    config["model"]["max_len"] = context
    return config


def window_report(requests: Sequence[Dict], sessions: int, w0: float,
                  w1: float, t0: float) -> Dict:
    """What the clients' records say of the window [w0, w1): were all the
    sessions decoding when it opened, was anything admitted from then on,
    and which sessions failed."""
    by_idx = {r["idx"]: r for r in requests}
    one_each = len(requests) == sessions \
        and set(by_idx) == set(range(sessions))
    firsts = [(r.get("token_times") or [None])[0] for r in requests]
    ready = one_each and all(
        r["sent"] is not None and r["sent"] < w0
        and first is not None and first < w0
        for r, first in zip(requests, firsts))
    admissions = sum(1 for r in requests
                     if r["idx"] >= sessions or r.get("done")
                     or (r["sent"] is not None and r["sent"] >= w0))
    failed = [r["idx"] for r in requests if r["idx"] < sessions and (
        r.get("error") or r.get("done") or not r.get("cut")
        or not any(w0 <= t < w1 for t in r.get("token_times") or []))]
    failed += [i for i in range(sessions) if i not in by_idx]
    seen = [f for r, f in zip(requests, firsts)
            if f is not None and r["idx"] < sessions]
    return {"sessions_ready": bool(ready), "window_admissions": admissions,
            "failed": sorted(set(failed)),
            "ready_s": max(seen) - t0 if seen else None}


def silences(arrivals: Sequence[float], w0: float, w1: float,
             longer_than: float = SILENCE_S) -> List[List[float]]:
    """[seconds from w0, length] of every gap of over `longer_than` seconds
    inside [w0, w1) between two token arrivals at ANY client (the window's
    edges close a gap): the machine's pauses as the clients feel them."""
    edges = [w0] + sorted(t for t in arrivals if w0 <= t < w1) + [w1]
    return [[a - w0, b - a] for a, b in zip(edges, edges[1:])
            if b - a > longer_than]


def sample_sessions(requests: Sequence[Dict], seed: int, w0: float,
                    w1: float) -> List[Dict]:
    """`N_CHECK` sessions, each with its tokens of before the window
    (`prefix`) and its first `N_TOKENS` of inside it (`judged`): the one
    with the longest context at the window's opening and a seeded draw of
    the others; fewer where fewer have `N_TOKENS` tokens inside."""
    import numpy as np

    pool = []
    for r in sorted(requests, key=lambda r: r["idx"]):
        times = r.get("token_times") or []
        first = sum(1 for t in times if t < w0)
        inside = sum(1 for t in times if w0 <= t < w1)
        if inside >= N_TOKENS and not r.get("error"):
            pool.append({"idx": r["idx"], "prompt_len": r["prompt_len"],
                         "prefix": r["tokens"][:first],
                         "judged": r["tokens"][first:first + N_TOKENS]})
    if not pool:
        return []
    longest = max(pool, key=lambda s: s["prompt_len"] + len(s["prefix"]))
    rest = [s for s in pool if s is not longest]
    rng = np.random.default_rng([int(seed), 0x5E5510])
    drawn = rng.choice(len(rest), size=min(N_CHECK - 1, len(rest)),
                       replace=False)
    return sorted([longest] + [rest[i] for i in drawn],
                  key=lambda s: s["idx"])


def run(cell: Dict, args, out_dir: str, allow_cpu: bool = False) -> Dict:
    import jax
    import numpy as np

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import Server, ServingConfig
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    traffic = dict(cell["traffic_file"])
    config = with_context(cell["config_file"], traffic)  # before any device
    dev, peaks = device.start(cell["chips"], allow_cpu)
    family = manifest.plugin("families", config["family"])
    model, serve = config["model"], config["serve"]
    cfg = family.make_config(model)
    devices = jax.devices()[:cell["chips"]]
    resident_at_start = device.resident_bytes(devices)
    sessions = int(traffic["clients"])
    weights_seed = int(traffic["weights_seed"])

    if args.trace:
        tracing.start_recording()
    slots = max(serve["decode_slots"])
    pool_tokens = slots * serve["kv_context_per_slot"]
    engine = server = child = None
    try:
        params, _ = family.init(cfg, weights_seed,
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
        for b in engine.prefill_buckets:
            _warm_request(port, traffic_mod.prompt_ids(
                args.seed, 10 ** 6 + b, min(b, model["max_len"] - 4),
                model["vocab_size"]), 3)

        t0 = time.monotonic() + 1.0
        w0 = t0 + float(traffic["lead_s"])
        w1 = w0 + float(args.seconds)
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
        c0 = dict(_counters(), load=engine.load(),
                  requests=engine.status()["requests"])
        _sleep_until(w1)
        c1 = dict(_counters(), load=engine.load())
        status_close = engine.status()
        c1["requests"], kv_close = (status_close["requests"],
                                    status_close["kv"])

        if args.trace:
            trace_dir = os.path.join(out_dir, "trace")
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profile_options())
            ta = time.monotonic()
            time.sleep(TRACE_S)
            tb = time.monotonic()
            jax.profiler.stop_trace()
        child.wait(timeout=60)
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

    plans = _served_plans(engine)
    resident = device.resident_bytes(devices)
    planned_total = resident + max(
        (p.get("temp", 0) + max(0, p.get("output", 0) - p.get("alias", 0))
         for p in plans.values()), default=0)
    dev["memory_peak_bytes"] = int(max(
        planned_total, device.runtime_peak_bytes(devices)))
    del engine, server
    gc.collect()
    resident_dropped = device.resident_bytes(devices)

    trace = program = scopes = None
    checks: Dict = {}
    prefills_recorded: Optional[int] = None
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
        # a prefill the program itself recorded inside the window: a step
        # record of a prompt's program, or a `decode.prefill` span that
        # ended there
        prefills_recorded = sum(
            1 for s in program["steps"] if s["kind"] in ADMISSION_STEPS) \
            + sum(1 for span in program["spans"]
                  if span[0] == "decode.prefill")
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
        live = [s["live_tokens"] for s in program_trace.collect(
            ta, tb)["steps"] if s["kind"] == "decode"]
        if live:
            trace["live_tokens_mean"] = float(np.mean(live))
            trace["decode_min_bytes"] = family.decode_step_min_bytes(
                model, trace["live_tokens_mean"])

    with open(job["out"]) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    win = window.stream_window(requests, w0, w1)
    report = window_report(requests, sessions, w0, w1, t0)
    # the engine's own view of the edges: nothing finished in between, and
    # every session resident at both
    engine_quiet = c0["requests"] == c1["requests"] \
        and tuple(c0["load"]) == tuple(c1["load"]) == (0, sessions)
    admissions = report["window_admissions"] + (0 if engine_quiet else 1) \
        + (prefills_recorded or 0)

    bad_ids = [r["idx"] for r in requests
               if not all(0 <= t < model["vocab_size"] for t in r["tokens"])]
    sample = sample_sessions(requests, args.seed, w0, w1)
    gap = exact = resident_at_reference = None
    reference_t0 = time.monotonic()
    if len(sample) == N_CHECK:
        params, _ = family.init(cfg, weights_seed)  # float32, the only set
        resident_at_reference = device.resident_bytes(devices)
        gap, exact = family.reference_gaps(
            params, model,
            [traffic_mod.prompt_ids(args.seed, s["idx"], s["prompt_len"],
                                    model["vocab_size"]) + s["prefix"]
             for s in sample],
            [s["judged"] for s in sample], model["max_len"])
        del params
    reference_s = time.monotonic() - reference_t0
    silent = silences(
        [t for r in requests for t in r.get("token_times") or []], w0, w1)
    silence_s = sum(length for _, length in silent)
    window_compiles = c1["compile_requests"] - c0["compile_requests"]
    correct = (gap is not None and gap <= config["logit_gap_tol"]
               and not bad_ids and window_compiles == 0
               and report["sessions_ready"] and admissions == 0)

    checks.update({
        "sessions": sessions, "failed_sessions": report["failed"][:8],
        "bad_token_ids": bad_ids[:8],
        "sampled": [s["idx"] for s in sample],
        "sampled_context": [s["prompt_len"] + len(s["prefix"])
                            for s in sample],
        "ref_exact_tokens": exact, "ref_tokens": N_CHECK * N_TOKENS,
        "reference_s": reference_s, "silence_s": silence_s,
        "silences": sorted(silent, key=lambda g: -g[1])[:8],
        "engine_requests_open": c0["requests"],
        "engine_requests_close": c1["requests"],
        "load_open": c0["load"], "load_close": c1["load"],
        "prefills_recorded_in_window": prefills_recorded,
        "tokens_in_window": win["tokens"],
        "context_per_slot": serve["kv_context_per_slot"],
        "memory": {
            "resident_bytes": resident, "plans": plans,
            "weight_bytes": weight_bytes,
            "kv_pool_bytes": kv_close["pool_bytes"],
            "resident_at_start": resident_at_start,
            "resident_after_build": resident_after_build,
            "resident_dropped": resident_dropped,
            "resident_at_reference": resident_at_reference,
            "kv_pool_tokens": pool_tokens,
            "kv_bytes_per_token": family.kv_bytes_per_token(model),
            "kv_live_tokens_close": kv_close["live_tokens"],
            "kv_blocks_used_close": kv_close["blocks_used"]},
        # each number compared beside its limit, last so that the end of
        # standard error holds them
        "compared": {
            "ref_max_logit_gap": [gap, config["logit_gap_tol"]],
            "sessions_ready_s": [report["ready_s"], float(traffic["lead_s"])],
            "sessions_ready": [report["sessions_ready"], True],
            "window_admissions": [admissions, 0],
            "compiles_in_window": [window_compiles, 0],
            "bad_token_ids": [len(bad_ids), 0]}})

    records = {
        "kind": "serve", "chips": cell["chips"], "peaks": peaks,
        "model": model, "kv_pool_tokens": pool_tokens,
        "window_s": w1 - w0, "window": win,
        "counters": {"open": c0, "close": c1},
        "planned_bytes": planned_total, "trace": trace,
        "program": program, "scopes": scopes,
        # what the readers of this kind's own metrics read; a `serve`
        # cell's records have no such key and give them nothing
        "sessions": {
            "ready_s": report["ready_s"],
            "silence_s": silence_s},
    }
    end_to_end = {"serve_tokens_per_s": win["tokens_per_s"],
                  "setup_s": setup_s}
    return {"correct": bool(correct), "attempted": sessions,
            "failed": len(report["failed"]), "end_to_end": end_to_end,
            "records": records, "device": dev, "checks": checks}
