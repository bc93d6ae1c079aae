"""BENCHMARK.json keeps to its contract, and the harness is driven by data:
a configuration, a traffic mix and a per-layer metric are added as NEW files
plus NEW entries, with no edit to a file that is there."""

import copy
import hashlib
import json
import os
import re
import shutil

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
    # ttft_p50_ms could not be held under a bound (PERF.md): per layer
    assert {m["name"] for m in bench["end_to_end"]} == {
        "train_tokens_per_s", "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert "ttft_p50_ms" in {m["name"] for m in bench["per_layer"]}
    assert [w["name"] for w in bench["workloads"]] == [
        "bert_base.pretrain128", "gpt2_large.chat_open",
        "gpt2_large.doc_closed", "bert_base.dp4"]
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


def test_a_cell_and_a_metric_are_added_as_new_files_only(tmp_path, bench):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree_digest(root / "benchmarks")

    # a later PR's additions: three new files ...
    cfg = json.load(open(root / "benchmarks/configs/gpt2_large.json"))
    cfg["name"], cfg["model"]["layers"] = "gpt2_xl_like", 48
    (root / "benchmarks/configs/gpt2_xl_like.json").write_text(
        json.dumps(cfg))
    (root / "benchmarks/traffic/burst_open.json").write_text(json.dumps({
        "kind": "serve", "loop": "open", "rate_per_s": 2.0,
        "prompt_len": {"dist": "fixed", "value": 64},
        "output_len": {"dist": "fixed", "value": 8}}))
    (root / "benchmarks/layer_metrics/ttft_spread_ms.py").write_text(
        "def read(rec):\n    t = rec['window']['ttft_s']\n"
        "    return 1000.0 * (max(t) - min(t)) if t else None\n")
    # ... and three new entries
    new = copy.deepcopy(bench)
    new["configs"].append({"name": "gpt2_xl_like", "source": "test",
                           "file": "benchmarks/configs/gpt2_xl_like.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "gpt2_xl_like.burst_open",
                             "config": "gpt2_xl_like",
                             "traffic": "burst_open", "chips": 1,
                             "why": "test"})
    new["per_layer"].append({
        "name": "ttft_spread_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "entry", "moves": "itl_p95_ms",
        "workloads": ["gpt2_xl_like.burst_open"]})

    bench_dir = str(root / "benchmarks")
    cell = manifest.find_cell(new, "gpt2_xl_like.burst_open", bench_dir)
    assert cell["config_file"]["model"]["layers"] == 48
    assert cell["traffic_file"]["rate_per_s"] == 2.0
    got = manifest.read_layer_metrics(
        new, "gpt2_xl_like.burst_open",
        {"kind": "other", "window": {"ttft_s": [0.1, 0.3]}}, bench_dir)
    assert got == {"ttft_spread_ms": {"value": pytest.approx(200.0),
                                      "unit": "ms"}}
    # a reader that finds nothing to read is left out of the line
    assert manifest.read_layer_metrics(
        new, "gpt2_xl_like.burst_open",
        {"kind": "other", "window": {"ttft_s": []}}, bench_dir) == {}

    # nothing that was there was edited
    for name in ("gpt2_xl_like.json", "burst_open.json",
                 "ttft_spread_ms.py"):
        for sub in ("configs", "traffic", "layer_metrics"):
            p = root / "benchmarks" / sub / name
            if p.exists():
                p.unlink()
    assert _tree_digest(root / "benchmarks") == before
