#!/usr/bin/env python3
"""A benchmark run with the PROGRAM's recording on: `run.py`'s run of a cell
with `paddle_tpu.observability.tracing` recording from before the engine is
built until the run ends, the program's spans and records collected into
`records` beside the harness's, and the per-layer metrics that read them
(`benchmarks/program_metrics.json`: entries in BENCHMARK.json's form)
reported beside the cell's own, from the same run.

    python3 benchmarks/trace_run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

It is what `kinds/serve.py` and `kinds/train.py` will do themselves once a
`benchmark` PR may edit them (PERF.md section 7 has the lines); until then
the driver's command, `run.py`, leaves the program's recording off and
reports only the metrics of BENCHMARK.json. `--trace 0` records without the
profiler's sub-window: the window's end-to-end metrics with recording ON,
which against `run.py --trace 0` of the same seed is the recording's cost.

Beside `engine_spans.jsonl` it writes `program_spans.jsonl`,
`engine_steps.jsonl`, `engine_requests.jsonl` (times relative to the
window's opening) and, traced, `device_scopes.json`. It fails loudly where
a served run recorded no `decode.turn` span in the window, or a traced run
shows no device op with a layer scope (an executable from the compile cache
that predates the scopes)."""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the engine loop's spans, innermost last where they nest; idle gaps of the
# device go to the innermost of these (and of the harness's three)
LOOP_SPANS = ("decode.turn", "decode.admit", "decode.grow", "decode.prefill",
              "decode.prefill.wait", "decode.dispatch", "decode.resolve",
              "decode.resolve.wait")


def run_recorded(runner, cell, args, out_dir, allow_cpu=False):
    """`runner.run` with the program's recording on; its result, with
    `records["program"]`, `records["scopes"]` and the idle gaps attributed
    to the innermost span."""
    from benchmarks.harness import program_trace, trace_reduce
    from paddle_tpu.observability import tracing

    if args.trace:
        # a traced run reads scopes, which are metadata: never load an
        # executable cached before a scope was added (untraced runs, and
        # `setup_s`, keep the keys they have)
        import jax

        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    tracing.start_recording()
    try:
        result = runner.run(cell, args, out_dir, allow_cpu)
    finally:
        tracing.stop_recording()
    records = result["records"]
    checks = result["checks"]
    serve = records["kind"] == "serve"
    if serve:
        with open(os.path.join(out_dir, "loadgen_job.json")) as f:
            job = json.load(f)
        w0 = job["t0"] + float(job["traffic"]["lead_s"])
        w1 = w0 + float(args.seconds)
        program = records["program"] = program_trace.collect(w0, w1)
        if not any(s[0] == "decode.turn" for s in program["spans"]):
            raise RuntimeError("the program recorded no decode.turn span "
                               "in the window: its recording is off, or "
                               "the engine loop lost its spans")
        _write_program(program, w0, out_dir)
        checks["recording"] = {
            "spans_per_s": len(program["spans"]) / (w1 - w0),
            "records_per_s": (len(program["steps"])
                              + len(program["requests"])) / (w1 - w0),
            "dropped_spans": program["dropped"]}
    if args.trace:
        xplane = trace_reduce.find_xplane(os.path.join(out_dir, "trace"))
        scopes = records["scopes"] = program_trace.reduce_scopes(xplane)
        with open(os.path.join(out_dir, "device_scopes.json"), "w") as f:
            json.dump(scopes, f, indent=1)
        if not scopes.get("scoped_ops"):
            raise RuntimeError(
                "no device op of the trace carries a layer scope: the "
                "executables predate the scopes (a compile cache keyed "
                "without metadata?)")
        if serve:
            gaps = _idle_gaps(xplane, runner.HOST_SPANS + LOOP_SPANS,
                              "engine_other")
            checks["idle_gaps_harness"] = records["trace"]["idle_gaps"]
            records["trace"]["idle_gaps"] = gaps
    return result


def _write_program(program, w0, out_dir):
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


def _idle_gaps(xplane, names, other):
    """`breakdown.idle_gaps` as trace_reduce gives it (worst device,
    window from the first to the last device op), each second given to the
    innermost span among `names`."""
    from benchmarks.harness import program_trace, trace_reduce

    loaded = trace_reduce.load_xplane(xplane, names)
    devs = {i: d for i, d in loaded["devices"].items() if d["ops"]}
    lo = min(a for d in devs.values() for _, a, _ in d["ops"])
    hi = max(b for d in devs.values() for _, _, b in d["ops"])
    per_dev = {i: trace_reduce.reduce_device(d["ops"], lo, hi)
               for i, d in devs.items()}
    worst = max(per_dev, key=lambda i: per_dev[i]["window_s"]
                - per_dev[i]["busy_s"])
    gaps = program_trace.attribute_innermost(
        per_dev[worst]["idle_gaps"], loaded["host"], other)
    return sorted(([n, g["seconds"]] for n, g in gaps.items()),
                  key=lambda x: -x[1]) + sorted(
        ([n + ".longest", g["longest"]] for n, g in gaps.items()),
        key=lambda x: -x[1])[:4]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    args.t_start, args.rate = T_START, None

    from benchmarks import run as bench_run
    from benchmarks.harness import device, manifest

    bench = manifest.load_manifest()
    with open(os.path.join(manifest.BENCH_DIR, "program_metrics.json")) as f:
        bench["per_layer"] = bench["per_layer"] + json.load(f)["per_layer"]
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell = manifest.find_cell(bench, args.workload)
    out_dir = os.path.join(ROOT, "bench_out", args.workload,
                           f"seed{args.seed}-trace{args.trace}-recorded")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    runner = manifest.plugin("kinds", cell["traffic_file"]["kind"])
    try:
        result = run_recorded(runner, cell, args, out_dir)
    except device.NoAccelerator as e:
        print(f"benchmarks/trace_run.py: {e}", file=sys.stderr)
        return 1
    # both groups of metrics, from the one run: the window is measured
    # with the profiler off in either case
    per_layer = dict(args.__dict__, trace=1)
    line = bench_run.emit(bench, argparse.Namespace(**per_layer), result)
    if not args.trace:
        line.pop("breakdown")
    line["end_to_end"] = result["end_to_end"]
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"line": line, "checks": result["checks"]}, f, indent=1,
                  default=str)
    print(json.dumps({"checks": result["checks"]}, default=str),
          file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
