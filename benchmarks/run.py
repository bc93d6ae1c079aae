#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration and traffic files by
name, and the runner of the traffic's kind; runs on the accelerator JAX
finds (none, or fewer chips than the cell asks for: exit 1, no result) and
prints as its LAST line of standard output one JSON object with the keys
`correct`, `attempted`, `failed`, `metrics`, `device` and, traced,
`breakdown`. With --trace 0 the metrics are the cell's end-to-end metrics,
with --trace 1 its per-layer metrics. `--rate` overrides an open-loop
traffic file's rate, for the knee sweep of benchmarks/README.md only."""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    args.t_start = T_START

    from benchmarks.harness import device, manifest

    bench = manifest.load_manifest()
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell = manifest.find_cell(bench, args.workload)
    out_dir = os.path.join(ROOT, "bench_out", args.workload,
                           f"seed{args.seed}-trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    runner = manifest.plugin("kinds", cell["traffic_file"]["kind"])
    try:
        result = runner.run(cell, args, out_dir)
    except device.NoAccelerator as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1

    line = emit(bench, args, result)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"line": line, "checks": result["checks"],
                   "end_to_end": result["end_to_end"]}, f, indent=1,
                  default=str)
    print(json.dumps({"checks": result["checks"]}, default=str),
          file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


def emit(bench, args, result) -> dict:
    """The result line of the contract from a runner's result."""
    from benchmarks.harness import manifest

    dev = dict(result["device"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        records = result["records"]
        line["metrics"] = manifest.read_layer_metrics(
            bench, args.workload, records)
        trace = records.get("trace") or {}
        dev["busy_s"] = trace.get("busy_s")
        dev["window_s"] = trace.get("window_s")
        line["breakdown"] = {"device_ops": trace.get("device_ops", []),
                             "idle_gaps": trace.get("idle_gaps", [])}
    else:
        line["metrics"] = {
            m["name"]: {"value": result["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in manifest.cell_metrics(bench, args.workload,
                                           "end_to_end")}
    line["device"] = dev
    return line


if __name__ == "__main__":
    sys.exit(main())
