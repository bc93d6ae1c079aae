"""BENCHMARK.json keeps to its contract, and the harness is driven by data:
a model family, a configuration, a traffic mix and a per-layer metric are
added as NEW files plus NEW entries, with no edit to a file that is there,
and the tests of this directory that read BENCHMARK.json still pass on the
manifest so extended: none pins the list of cells."""

import copy
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def test_manifest_has_exactly_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f'{w["config"]}.{w["traffic"]}'
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_and_units_use_the_allowed_characters(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])


def test_the_issue_names_are_all_there(bench):
    """The contract, not a list: a later PR's cell must not break this."""
    # ttft_p50_ms could not be held under a bound (PERF.md): per layer
    assert {m["name"] for m in bench["end_to_end"]} == {
        "train_tokens_per_s", "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert "ttft_p50_ms" in {m["name"] for m in bench["per_layer"]}
    names = [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    assert set(names) >= {"bert_base.pretrain128", "gpt2_large.chat_open",
                          "gpt2_large.doc_closed", "bert_base.dp4"}
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["name"] == f'{w["config"]}.{w["traffic"]}'
        assert w["config"] in configs
    assert configs == {w["config"] for w in bench["workloads"]}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in manifest.cell_metrics(
            bench, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layer = manifest.cell_metrics(bench, w["name"], "per_layer")
        assert layer, w["name"]
        for m in layer:  # what a layer metric moves, its cell reports
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_entry_has_its_file(bench):
    for w in bench["workloads"]:
        cell = manifest.find_cell(bench, w["name"])
        assert cell["config_file"]["model"] and cell["traffic_file"]["kind"]
        manifest.plugin("kinds", cell["traffic_file"]["kind"])
        manifest.plugin("families", cell["config_file"]["family"])
    for m in bench["per_layer"]:
        assert manifest.layer_metric_reader(m["name"]) is not None, m


def _tree_digest(top):
    h = hashlib.sha256()
    for d, _, files in sorted(os.walk(top)):
        for f in sorted(files):
            h.update(os.path.relpath(os.path.join(d, f), top).encode())
            with open(os.path.join(d, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


FAMILY_FILE = '''"""A later PR's model family: plain functions under a new
name (here it serves the program's GPT through them, which is all a tiny CPU
rehearsal needs; a real one brings its own model and reference)."""
from benchmarks.families.gpt import (decode_step_min_bytes, init,
                                     kv_bytes_per_token, make_config,
                                     reference_gaps)

__all__ = ["decode_step_min_bytes", "init", "kv_bytes_per_token",
           "make_config", "reference_gaps"]
'''

REHEARSAL = '''"""The added cell through the copy's own harness, on the CPU."""
import json, sys, time, types
from benchmarks.harness import manifest
from benchmarks.kinds import serve

bench = manifest.load_manifest()
cell = manifest.find_cell(bench, "tiny_next.burst_closed")
assert cell["config_file"]["family"] == "next_arch"
args = types.SimpleNamespace(seed=2 ** 31 + 3, seconds=1.5, trace=0,
                             rate=None, t_start=time.monotonic())
res = serve.run(cell, args, sys.argv[1], allow_cpu=True)
assert res["correct"], res["checks"]
got = manifest.read_layer_metrics(bench, cell["name"], res["records"])
assert set(got) >= {"ttft_spread_ms", "slot_occupancy.next"}, got
print(json.dumps({"attempted": res["attempted"], "metrics": sorted(got)}))
'''


def test_a_family_a_cell_and_a_metric_are_added_as_new_files_only(
        tmp_path, bench):
    """A scratch copy of the benchmark's two directories, a later PR's
    five new files and five new entries, and nothing that was there
    edited: the new cell runs there (a tiny serve rehearsal through the
    new family), and the manifest tests pass on the extended manifest."""
    root = tmp_path / "checkout"
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=ignore)
    shutil.copytree(os.path.join(ROOT, "tests", "benchmarks"),
                    root / "tests" / "benchmarks", ignore=ignore)
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"), root / "tests")
    before = (_tree_digest(root / "benchmarks"),
              _tree_digest(root / "tests" / "benchmarks"))

    # a later PR's additions: a family, a configuration, a traffic mix and
    # two readers ...
    from tests.benchmarks.test_benchmark_run import SERVE_MIX, TINY_GPT

    added = {
        "benchmarks/families/next_arch.py": FAMILY_FILE,
        "benchmarks/configs/tiny_next.json": json.dumps(
            dict(TINY_GPT, name="tiny_next", family="next_arch")),
        "benchmarks/traffic/burst_closed.json": json.dumps(
            dict(SERVE_MIX, clients=2)),
        "benchmarks/layer_metrics/ttft_spread_ms.py":
            "def read(rec):\n    t = rec['window']['ttft_s']\n"
            "    return 1000.0 * (max(t) - min(t)) if t else None\n",
    }
    for path, text in added.items():
        assert not (root / path).exists()
        (root / path).write_text(text)
    # ... and their entries (the second metric is an old reader's quantity
    # split for the new cell: no file at all)
    new = copy.deepcopy(bench)
    new["configs"].append({"name": "tiny_next", "source": "test",
                           "file": "benchmarks/configs/tiny_next.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "tiny_next.burst_closed",
                             "config": "tiny_next",
                             "traffic": "burst_closed", "chips": 1,
                             "why": "test"})
    next(m for m in new["end_to_end"]
         if m["name"] == "serve_tokens_per_s")["workloads"].append(
        "tiny_next.burst_closed")
    for name in ("ttft_spread_ms", "slot_occupancy.next"):
        new["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "entry",
            "moves": "serve_tokens_per_s",
            "workloads": ["tiny_next.burst_closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    bench_dir = str(root / "benchmarks")
    cell = manifest.find_cell(new, "tiny_next.burst_closed", bench_dir)
    assert cell["config_file"]["family"] == "next_arch"
    assert cell["traffic_file"]["clients"] == 2
    got = manifest.read_layer_metrics(
        new, "tiny_next.burst_closed",
        {"kind": "other", "window": {"ttft_s": [0.1, 0.3]}}, bench_dir)
    assert got == {"ttft_spread_ms": {"value": pytest.approx(200.0),
                                      "unit": "ms"}}
    # a reader that finds nothing to read is left out of the line
    assert manifest.read_layer_metrics(
        new, "tiny_next.burst_closed",
        {"kind": "other", "window": {"ttft_s": []}}, bench_dir) == {}

    # the copy's own harness runs the new cell, and the tests of this
    # directory that read BENCHMARK.json pass on the extended manifest
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), ROOT]))
    script = tmp_path / "rehearse.py"
    script.write_text(REHEARSAL)
    out = tmp_path / "out"
    out.mkdir()
    run = subprocess.run([sys.executable, str(script), str(out)],
                         cwd=root, env=env, capture_output=True, text=True,
                         timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["attempted"] >= 2
    tests = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", "not new_files_only",
         "tests/benchmarks/test_benchmark_manifest.py",
         "tests/benchmarks/test_benchmark_program_trace.py::"
         "test_the_entries_that_read_the_programs_recording"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert tests.returncode == 0, tests.stdout[-3000:]
    assert " passed" in tests.stdout and "failed" not in tests.stdout

    # nothing that was there was edited
    for path in added:
        (root / path).unlink()
    shutil.rmtree(root / "bench_out", ignore_errors=True)
    for d, _, _ in list(os.walk(root)):
        if os.path.basename(d) == "__pycache__":
            shutil.rmtree(d)
    assert (_tree_digest(root / "benchmarks"),
            _tree_digest(root / "tests" / "benchmarks")) == before
