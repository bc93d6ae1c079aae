"""The command end to end, off the chip: it fails without an accelerator
and for an unknown device_kind, its last line has exactly the contract's
keys, and tiny CPU rehearsals drive both runners through the program's real
entry points (counts and control flow only: a CPU run gives no device
number, and run.py itself never allows one)."""

import json
import os
import subprocess
import sys
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, manifest

ROOT = manifest.ROOT

TINY_BERT = {
    "family": "bert",
    "model": {"vocab_size": 1024, "hidden": 64, "layers": 2, "heads": 4,
              "mlp_dim": 128, "max_len": 64, "type_vocab": 2,
              "dropout": 0.1, "dtype": "bfloat16"},
    "train": {"learning_rate": 1e-3,
              "strategy": {"shard_optimizer_states": True}},
    "loss_rel_tol": 0.02, "forward_rel_tol": 0.05}
TINY_GPT = {
    "family": "gpt",
    "model": {"vocab_size": 512, "hidden": 64, "layers": 4, "heads": 4,
              "mlp_dim": 128, "max_len": 128, "dtype": "bfloat16"},
    "serve": {"precision": "bf16", "block_size": 16, "decode_slots": [4],
              "kv_context_per_slot": 128, "eos_id": None, "max_queue": 64},
    "logit_gap_tol": 0.5}


def _args(tmp_path, trace=0, seconds=1.5):
    return types.SimpleNamespace(seed=2 ** 31 + 7, seconds=seconds,
                                 trace=trace, rate=None,
                                 t_start=time.monotonic())


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert device.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no default"):
        device.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.load_peaks("cpu")
    with pytest.raises(KeyError):
        device.load_peaks("_source")


def test_a_run_without_a_chip_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "bert_base.pretrain128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_asking_for_more_chips_than_there_are_fails():
    with pytest.raises(device.NoAccelerator):
        device.require(64, allow_cpu=True)
    with pytest.raises(device.NoAccelerator):
        device.require(1)  # the tests' backend is the CPU


def _fake_result():
    return {"correct": True, "attempted": 10, "failed": 0,
            "end_to_end": {"train_tokens_per_s": 123.5, "setup_s": 9.25},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 5},
            "records": {"kind": "train", "chips": 1, "window_s": 4.0,
                        "input_wait_s": 0.04, "peaks": None,
                        "chunks": [{"seconds": 2.0, "steps": 10}] * 2,
                        "rates": {"stall_share": 0.0,
                                  "tokens_per_s": 123.5,
                                  "steady_tokens_per_s": 124.0},
                        "trace": {"devices_seen": 1, "busy_s": 1.5,
                                  "window_s": 2.0, "idle_share_worst": 0.25,
                                  "device_ops": [["fusion", 1.5]],
                                  "idle_gaps": [["sync", 0.5]]}},
            "checks": {}}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_has_exactly_the_contract_keys(trace):
    bench = manifest.load_manifest()
    args = types.SimpleNamespace(workload="bert_base.pretrain128",
                                 trace=trace)
    line = json.loads(json.dumps(bench_run.emit(bench, args,
                                                _fake_result())))
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if trace else keys)
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["device"]) == (dev | {"busy_s", "window_s"}
                                   if trace else dev)
    if trace:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["metrics"]["device_idle_share.train"] == {
            "value": 0.25, "unit": "share"}
        assert line["metrics"]["step_p50_ms"]["value"] == 200.0
        assert line["metrics"]["train_steady_tokens_per_s"]["value"] == 124.0
        assert "mfu.train" not in line["metrics"]  # nothing to read: left out
        assert "setup_s" not in line["metrics"]
    else:
        assert line["metrics"] == {
            "train_tokens_per_s": {"value": 123.5, "unit": "tokens/s"},
            "setup_s": {"value": 9.25, "unit": "s"}}


@pytest.mark.parametrize("chips", [1, 4])
def test_tiny_train_rehearsal(tmp_path, chips):
    from benchmarks.kinds import train

    cell = {"name": "tiny.train", "chips": chips, "config_file": TINY_BERT,
            "traffic_file": {
                "kind": "train", "mesh": {"dp": chips}, "seq_len": 32,
                "batch_per_chip": 8, "mask_rate": 0.15, "chunk_steps": 4}}
    res = train.run(cell, _args(tmp_path), str(tmp_path), allow_cpu=True)
    checks = res["checks"]
    assert checks["loss_rel_diff"] <= checks["loss_rel_tol"], checks
    assert checks["forward_rel_diff"] <= checks["forward_rel_tol"], checks
    assert checks["losses_finite"] and checks["compiles_in_window"] == 0
    # at this toy size a handful of steps need not lower the loss: the
    # verdict has to follow the comparison, whichever way it went
    assert res["correct"] == (checks["last_chunk_loss"]
                              < checks["first_chunk_loss"]), checks
    assert res["device"]["platform"] == "cpu"  # never reported as a chip
    chunks = [json.loads(x) for x in open(tmp_path / "chunks.jsonl")]
    assert len(chunks) >= 2 and res["attempted"] == 4 * len(chunks)
    assert all(c["tokens"] == 4 * 8 * chips * 32 for c in chunks)
    rec = res["records"]
    # the window is whole chunks, and the rate is ALL of them over ALL of
    # it; the rate without the slowest chunk stands beside it, per layer
    assert rec["window_s"] == pytest.approx(
        chunks[-1]["t0_s"] + chunks[-1]["seconds"])
    assert rec["window_s"] >= sum(c["seconds"] for c in chunks)
    assert res["end_to_end"]["train_tokens_per_s"] == pytest.approx(
        len(chunks) * chunks[0]["tokens"] / rec["window_s"])
    kept = sorted(c["seconds"] for c in chunks)[:-1]
    assert manifest.layer_metric_reader("train_steady_tokens_per_s")(
        rec) == pytest.approx(len(kept) * chunks[0]["tokens"] / sum(kept))


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_tiny_serve_rehearsal(tmp_path, loop):
    from benchmarks.kinds import serve

    mix = {"kind": "serve", "loop": loop, "rate_per_s": 6.0, "clients": 3,
           "table_size": 24,
           "prompt_len": {"dist": "loguniform", "lo": 4, "hi": 60},
           "output_len": {"dist": "loguniform", "lo": 16, "hi": 32},
           "prefill_buckets": [32, 64], "lead_s": 0.5}
    cell = {"name": "tiny.serve", "chips": 1, "config_file": TINY_GPT,
            "traffic_file": mix}
    res = serve.run(cell, _args(tmp_path, seconds=2.0), str(tmp_path),
                    allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert res["checks"]["compiles_in_window"] == 0
    recs = [json.loads(x) for x in open(tmp_path / "requests.jsonl")]
    assert all(r["sent"] >= r["due"] for r in recs)
    win = res["records"]["window"]
    # tokens RECEIVED inside the window, whichever request they belong to
    assert win["tokens"] == round(
        res["end_to_end"]["serve_tokens_per_s"] * 2.0)
    assert win["tokens"] > len([r for r in recs if r["done"]])
    assert (tmp_path / "engine_spans.jsonl").exists()
    bench = manifest.load_manifest()
    for name in ("decode_step_p50_ms", "prefill_share", "slot_occupancy",
                 "queue_wait_p50_ms", "gen_late_p95_ms", "ttft_p50_ms",
                 "prefill_gap_share", "kv_used_share"):
        assert manifest.layer_metric_reader(name)(res["records"]) \
            is not None, name
    assert {m["name"] for m in bench["per_layer"]} >= {"slot_occupancy"}
    assert 0.0 < manifest.layer_metric_reader("kv_used_share")(
        res["records"]) <= 1.0
    mem = res["checks"]["memory"]
    assert mem["kv_live_tokens_max"] <= mem["kv_pool_tokens"] == 4 * 128


def test_the_serve_harness_fails_loudly_when_its_spans_lose_their_hold():
    from benchmarks.kinds import serve

    class Renamed:  # serving/decode.py renamed a method the harness wraps
        def _prefill_one(self, req): ...
        def _dispatch(self, ids, C): ...

    with pytest.raises(RuntimeError, match="_resolve"):
        serve.instrument(Renamed(), [])
