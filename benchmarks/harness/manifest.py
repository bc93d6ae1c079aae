"""Everything the harness knows about cells, configurations, traffic mixes
and per-layer metrics it finds BY NAME: `BENCHMARK.json` at the root, then
`configs/<configuration>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.py`, `kinds/<kind>.py`, `families/<family>.py`.
No table in code lists them, so a later PR adds files and entries only."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: Dict, workload: str, bench_dir: str = BENCH_DIR
              ) -> Dict:
    """The cell named `workload` with its configuration and traffic files
    read: {"name", "chips", "config": {...}, "traffic": {...}}."""
    cells = [w for w in manifest["workloads"] if w["name"] == workload]
    if not cells:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}")
    cell = dict(cells[0])
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    root = os.path.dirname(bench_dir)
    cell["config_file"] = _load_json(os.path.join(root, cfg_entry["file"]))
    cell["traffic_file"] = _load_json(os.path.join(
        bench_dir, "traffic", cell["traffic"] + ".json"))
    return cell


def cell_metrics(manifest: Dict, workload: str, group: str) -> List[Dict]:
    """The metrics of `group` ("end_to_end" | "per_layer") that `workload`
    reports: those without a `workloads` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def layer_metric_reader(name: str, bench_dir: str = BENCH_DIR
                        ) -> Optional[Callable]:
    """`read(records) -> number | None` of `layer_metrics/<name>.py`, or,
    where one quantity is split by the end-to-end metric it moves
    (`device_idle_share.train`, `.serve`), of the file named by the part
    before the first dot."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(bench_dir, "layer_metrics", stem + ".py")
        if os.path.exists(path):
            break
    else:
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmarks_layer_metric_" + name.replace(".", "_").replace(
            "-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(manifest: Dict, workload: str, records: Dict,
                       bench_dir: str = BENCH_DIR) -> Dict:
    """{"name": {"value", "unit"}} for every per-layer metric of the cell
    whose reader finds something to read; the others are left out."""
    out = {}
    for m in cell_metrics(manifest, workload, "per_layer"):
        read = layer_metric_reader(m["name"], bench_dir)
        value = read(records) if read else None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def plugin(group: str, name: str):
    """The module `benchmarks.<group>.<name>` (a traffic kind's runner or a
    model family's adapter), found by the name a data file gives."""
    return importlib.import_module(f"benchmarks.{group}.{name}")
