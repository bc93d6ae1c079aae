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


SERVE_MIX = {"kind": "serve", "loop": "closed", "rate_per_s": 6.0,
             "clients": 3, "table_size": 24,
             "prompt_len": {"dist": "loguniform", "lo": 4, "hi": 60},
             "output_len": {"dist": "loguniform", "lo": 16, "hi": 32},
             "prefill_buckets": [32, 64], "lead_s": 0.5}


def _serve_cell(**mix):
    return {"name": "tiny.serve", "chips": 1, "config_file": TINY_GPT,
            "traffic_file": dict(SERVE_MIX, **mix)}


def weights_held_once(mem) -> bool:
    """The serve kind's live device bytes (`checks.memory`), against one
    copy of the weights at a time: the served set plus the pools while the
    engine lives, nothing of them once it is dropped, and then the
    reference's float32 set alone. The slack is a quarter of the served
    weights: a second copy of them, in any precision, does not fit it."""
    slack = mem["weight_bytes"] // 4
    base = mem["resident_at_start"]
    served = mem["weight_bytes"] + mem["kv_pool_bytes"]
    return (mem["resident_after_build"] - base <= served + slack
            and mem["resident_bytes"] - base <= served + slack
            and mem["resident_dropped"] - base <= slack
            and mem["resident_at_reference"] - base
            <= 2 * mem["weight_bytes"] + slack)   # float32 of a bf16 set


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_tiny_serve_rehearsal(tmp_path, loop):
    from benchmarks.kinds import serve

    res = serve.run(_serve_cell(loop=loop), _args(tmp_path, seconds=2.0),
                    str(tmp_path), allow_cpu=True)
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
    # an untraced run records nothing of the program and wraps nothing
    assert res["records"]["program"] is None
    assert not (tmp_path / "program_spans.jsonl").exists()
    for name in ("slot_occupancy", "gen_late_p95_ms", "ttft_p50_ms"):
        assert manifest.layer_metric_reader(name)(res["records"]) \
            is not None, name
    for name in ("engine_step_p50_ms", "prefill_gap_share",
                 "kv_block_used_share", "decode_compute_share"):
        assert manifest.layer_metric_reader(name)(res["records"]) is None
    mem = res["checks"]["memory"]
    assert mem["kv_live_tokens_close"] <= mem["kv_pool_tokens"] == 4 * 128
    # the weights once at a time: bf16 under the engine, float32 for the
    # reference only after engine, server and pools are gone
    assert mem["weight_bytes"] > 0 and mem["kv_pool_bytes"] > 0
    assert weights_held_once(mem), mem


def test_a_second_copy_of_the_weights_fails_the_rehearsal(tmp_path,
                                                          monkeypatch):
    """What the kind did until PR 26: float32 parameters on the device
    while the engine casts its own copy."""
    from benchmarks.families import gpt as gpt_family
    from benchmarks.kinds import serve

    init, kept = gpt_family.init, []

    def init_keeping_float32(cfg, seed, dtype=None):
        if dtype is not None:
            kept.append(init(cfg, seed)[0])
        return init(cfg, seed, dtype)

    monkeypatch.setattr(gpt_family, "init", init_keeping_float32)
    res = serve.run(_serve_cell(), _args(tmp_path, seconds=1.0),
                    str(tmp_path), allow_cpu=True)
    assert res["correct"] and kept
    assert not weights_held_once(res["checks"]["memory"])


def test_init_on_device_casts_inside_its_one_program():
    """The served weights are the float32 ones rounded once: bit-identical
    to casting the float32 set afterwards, so the engine's tokens do not
    move; integer leaves are left alone."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import gpt as gpt_family

    cfg = gpt_family.make_config(TINY_GPT["model"])
    f32, axes = gpt_family.init(cfg, 2 ** 31 + 5)
    b16, axes16 = gpt_family.init(cfg, 2 ** 31 + 5, dtype="bfloat16")
    assert axes == axes16 and set(f32) == set(b16)
    for k, v in f32.items():
        assert v.dtype == jnp.float32 and b16[k].dtype == jnp.bfloat16, k
        assert np.array_equal(np.asarray(v.astype(jnp.bfloat16)),
                              np.asarray(b16[k])), k


def _traced_args(tmp_path):
    return _args(tmp_path, trace=1, seconds=1.0)


def test_a_traced_serve_run_fails_loudly_without_a_decode_turn_span(
        tmp_path, monkeypatch):
    """The program's recording off (or an engine loop that lost its spans)
    under a traced run: no metric is silently left out."""
    from benchmarks.kinds import serve
    from paddle_tpu.observability import tracing

    monkeypatch.setattr(serve, "TRACE_S", 0.2)
    monkeypatch.setattr(tracing, "start_recording", lambda clear=True: None)
    with pytest.raises(RuntimeError, match="decode.turn"):
        serve.run(_serve_cell(), _traced_args(tmp_path), str(tmp_path),
                  allow_cpu=True)


def test_a_traced_run_fails_loudly_without_a_scoped_device_op(tmp_path,
                                                              monkeypatch):
    """On the CPU the profiler's trace holds no device op at all: the
    traced run raises instead of reporting per-layer metrics without the
    scopes (on the chip: executables that predate the scopes)."""
    from benchmarks.kinds import serve

    monkeypatch.setattr(serve, "TRACE_S", 0.2)
    with pytest.raises(RuntimeError, match="layer scope"):
        serve.run(_serve_cell(), _traced_args(tmp_path), str(tmp_path),
                  allow_cpu=True)
    from paddle_tpu.observability import tracing
    assert not tracing.recording     # stopped on the way out
