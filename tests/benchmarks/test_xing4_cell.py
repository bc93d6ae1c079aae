"""The cell `xing4_29b_a4b.ctx12k_sessions` off the chip: its configuration
file against its source's keys (it differs in `reduced` and nowhere else),
the cell found with its readers and the traffic ISSUE 45 gives, its byte
counts against the program's shapes, its new readers on records made by
hand (and on the other families' records and the parent's program:
nothing, and no error), the scopes its family registers, and a tiny traced
rehearsal through the `sessions` kind."""

import copy
import dataclasses
import inspect
import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import xing4 as family
from benchmarks.harness import manifest, xing4_shapes as shapes
from benchmarks.kinds import sessions
from tests.benchmarks.test_nemotron_cell import jax_cache_config  # noqa: F401

CELL = "xing4_29b_a4b.ctx12k_sessions"

PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "xing4_29b_a4b.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_configuration_is_the_source_but_for_what_it_lists(config):
    from paddle_tpu.models import xing4

    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"]) \
        == {"num_hidden_layers", "max_position_embeddings",
            "num_nextn_predict_layers"}
    assert (config["num_hidden_layers"], config["max_position_embeddings"],
            config["num_nextn_predict_layers"]) == (6, 20480, 0)
    for key, published in (("num_hidden_layers", "40 -> 6"),
                           ("max_position_embeddings", "262144 -> 20480"),
                           ("num_nextn_predict_layers", "1 -> 0")):
        assert config["reduced_why"][key].startswith(published)
    model = config["model"]
    for ours, theirs in config["source_keys"].items():
        assert model[ours] == config[theirs], ours
    for ours, theirs in config["rope_scaling_keys"].items():
        assert model[ours] == config["rope_scaling"][theirs], ours
    assert config["rope_scaling"]["type"] == "yarn"
    assert set(config["assumed"]) >= {
        "streams_in_and_out", "map_rms", "map_columns", "clamp_and_order",
        "map_precision", "rope", "map_draws", "init", "layer"}
    assert "mtp" in config["not_served"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "xing4_29b_a4b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/xing4_29b_a4b.json"
    # no width is cut
    assert not [k for k in config["reduced"]
                if k.endswith(("_size", "_dim", "_rank", "_heads"))]
    assert config["logit_gap_tol_reason"] != "TO BE SET FROM CHIP READINGS"
    assert config["deployment"]
    # the program's defaults are the published model
    full = dataclasses.asdict(xing4.Xing4Config())
    for key, value in model.items():
        if key not in ("layers", "max_len"):
            assert full[key] == value, key
    assert (full["layers"], full["max_len"]) == (40, 262144)
    # the shared block's keys are spelled as `joyai_llm_flash.json` spells
    # them, so that its readers serve this model
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "joyai_llm_flash.json")) as f:
        assert set(json.load(f)["model"]) <= set(model)


def test_the_cell_is_found_with_its_readers_and_the_issues_traffic(config):
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    assert cell["chips"] == 1 and mix["kind"] == "sessions"
    assert mix["clients"] == mix["table_size"] == 32
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 6144,
                                 "hi": 12288}
    assert mix["output_len"] == {"dist": "fixed", "value": 8192}
    assert mix["prefill_buckets"] == [8192, 10240, 12288]
    assert mix["context_per_slot"] == 20480
    assert mix["weights_seed"] == 20261003
    assert mix["lead_s"] % 5 == 0 and mix["lead_s"] >= 10
    assert mix["note"] != "TO BE WRITTEN FROM CHIP READINGS"
    served = sessions.with_context(cell["config_file"], mix)
    serve = served["serve"]
    assert serve["kv_context_per_slot"] == 20480 == served["model"]["max_len"]
    assert serve["decode_slots"] == [32] == [mix["clients"]]
    model = served["model"]
    assert all(b % model["prompt_slice"] == 0
               for b in mix["prefill_buckets"])
    assert model["prompt_slice"] % serve["block_size"] == 0
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    new = {"mhc_share": "residual path", "mhc_roofline": "residual path",
           "mhc_col_err": "residual path",
           "latent_attention_roofline.xing4": "decode kernels",
           "latent_attention_share.xing4": "decode kernels",
           "expert_layer_roofline.xing4": "expert layer",
           "moe_share.xing4": "expert layer",
           "expert_load_max_over_mean.xing4": "expert layer",
           "window_admissions.xing4": "entry",
           "sessions_ready_s.xing4": "boot",
           "stream_silence_share.xing4": "entry"}
    for name, layer in new.items():
        assert per_layer[name]["layer"] == layer, name
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == (
            "setup_s" if name.startswith("sessions_ready_s")
            else "serve_tokens_per_s")
        assert manifest.layer_metric_reader(name) is not None
    assert {"engine_step_p50_ms.tput", "decode_step_roofline.tput",
            "decode_compute_share.tput", "slot_occupancy",
            "engine_host_share.tput", "stream_gap_p95_ms",
            "kv_block_used_share.tput", "device_idle_share.serve_tput",
            "hbm_planned_share.serve_tput", "setup_first_program_s",
            "setup_compile_s", "setup_lower_s", "setup_cache_misses",
            "setup_engine_warm_s.sessions"} <= set(per_layer)
    assert len(per_layer) == 25
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200
    assert bench["workloads"][-1] == entry      # appended, nothing moved
    # where the byte counts' slot count comes from (families/xing4.py)
    assert inspect.signature(shapes.decode_step_min_bytes).parameters[
        "slots"].default == max(serve["decode_slots"])


def test_the_byte_counts_follow_the_programs_shapes(config):
    import jax

    from paddle_tpu.models import xing4
    from paddle_tpu.serving.kv_cache import KVCacheConfig

    model = config["model"]
    cfg = family.make_config(model)
    made = jax.eval_shape(lambda k: xing4.init(k, cfg)[0], jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in made.values())
    assert shapes.param_count(model) == n
    assert n == pytest.approx(4175.9e6, rel=1e-4)       # 8.35 GB in bf16
    # the issue's arithmetic, piece by piece
    assert shapes.joyai_shapes.attention_params(model) == pytest.approx(
        28.41e6, rel=1e-3)
    assert 2 * shapes.map_params(model) == pytest.approx(0.69e6, rel=1e-2)
    assert shapes.joyai_shapes.dense_mlp_params(model) == 3 * 3584 * 9216
    assert shapes.joyai_shapes.expert_params(model) == 3 * 3584 * 1024
    assert made["blk.hc_attn.phi"].shape == (4, 24, 14336)
    assert made["dense.hc_mlp.b_res"].shape == (2, 4, 4)
    # what a sequence holds, as the engine's own geometry says
    sm = cfg.serve_model()
    serve = config["serve"]
    kv = KVCacheConfig(layers=sm.kv_layers, widths=sm.stored,
                       max_len=20480, block_size=serve["block_size"],
                       num_blocks=32 * 1280 + 1)
    assert family.kv_bytes_per_token(model) == 6 * 1280 \
        == sm.kv_layers * kv.bytes_per_token()
    assert kv.pool_bytes() == pytest.approx(5.03e9, rel=1e-2)
    assert serve["stored"] == {"latent": sm.stored[0],
                               "rope_key_lanes": sm.stored[1]}
    assert sm.prompt_slice == 2048 == model["prompt_slice"]
    assert sm.describe() == {"residual_streams": 4, "sinkhorn_iters": 20,
                             "carried_lanes": 14336}
    # a 32-row step over 368k live tokens: 9.5 GB, the weights 6.7 of them
    # (55.9 of 64 experts a layer), the latent cache 2.5, the maps and the
    # streams 0.03
    assert shapes.expected_experts_hit(model, 32) == pytest.approx(55.9,
                                                                   abs=0.05)
    least = family.decode_step_min_bytes(model, 368000.0)
    weights = shapes.always_read_bytes(model) \
        + 4 * shapes.expected_experts_hit(model, 32) * 3 * 3584 * 1024 * 2
    assert weights == pytest.approx(6.7e9, rel=0.02)
    assert least == pytest.approx(
        weights + 368000 * 6 * 576 * 2 + 12 * 2 * 32 * 14336 * 2, rel=1e-6)
    assert shapes.mhc_min_bytes(model, 32) == pytest.approx(
        12 * (14336 * 24 + 27 + 2 * 32 * 14336) * 2)
    assert shapes.mhc_min_bytes(model, 32) == pytest.approx(30.3e6, rel=0.01)


# -- the readers -------------------------------------------------------------

NEW = ("mhc_share", "mhc_roofline", "mhc_col_err")


def _records(model, steps, mhc=True):
    """A traced run's records; `mhc`: whether the program's ops carry the
    residual path's scopes (the parent's and the other families' do
    not)."""
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    by_scope = {"mlp": 0.5, "attention": 0.2, "qkv": 0.05, "proj": 0.02,
                "ln": 0.01, "head": 0.1, "layers.other": 0.02}
    if mhc:
        by_scope.update({"mhc_map": 0.06, "mhc_pre": 0.01, "mhc_post": 0.02,
                         "mhc": 0.01})
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": 368000.0,
                  "modules": {decode: {"count": 100, "median_s": 0.01},
                              prefill: {"count": 5, "median_s": 0.1}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 1.0, "by_scope": by_scope},
            prefill: {"total_s": 0.5, "by_scope": {"mlp": 0.3,
                                                   "mhc_map": 0.2}}}},
        "program": {"steps": steps}}


def _steps(err=3e-3):
    return [{"kind": "decode", "slots": 32, "live": 32, "experts_hit": 224,
             "expert_load_max": 7, "mhc_col_err": err * (1 + i % 3)}
            for i in range(10)] + [{"kind": "prefill", "slots": 1}]


def test_the_new_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    # the scopes nest: the path's seconds are the four together
    assert read("mhc_share")(rec) == pytest.approx(0.10)
    # 30.3 MB over 819 GB/s = 37 us, against 0.1 s / 100 steps = 1 ms
    assert read("mhc_roofline")(rec) == pytest.approx(
        100 * shapes.mhc_min_bytes(model, 32) / 819e9 / 0.001)
    assert 3.5 < read("mhc_roofline")(rec) < 3.9
    assert read("mhc_col_err")(rec) == pytest.approx(9e-3)
    # the shared block's readers serve the cell under their twins
    assert read("latent_attention_share.xing4")(rec) == pytest.approx(0.2)
    assert read("moe_share.xing4")(rec) == pytest.approx(0.5)
    assert read("latent_attention_roofline.xing4")(rec) == pytest.approx(
        100 * shapes.latent_attention_min_bytes(model, 368000.0)
        / 819e9 / 0.002)
    assert read("expert_layer_roofline.xing4")(rec) == pytest.approx(
        100 * shapes.mlp_min_bytes(model, 224) / 819e9 / 0.005)
    assert read("expert_load_max_over_mean.xing4")(rec) == pytest.approx(
        7 / (32 * 4 / 64))
    rec["sessions"] = {"ready_s": 11.5, "silence_s": 0.4}
    rec["window_s"] = 40.0
    assert read("window_admissions.xing4")(rec) == 1    # the prefill record
    assert read("sessions_ready_s.xing4")(rec) == 11.5
    assert read("stream_silence_share.xing4")(rec) == pytest.approx(0.01)


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """The parent's program and the other families': no `hc_mult` in the
    model group, no `mhc` scope on any op, step records without the
    counter, no trace: the metric is left out, nothing raises."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "joyai_llm_flash.json")) as f:
        joyai = json.load(f)["model"]
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    plain = [{"kind": "decode", "slots": 16, "live": 16, "experts_hit": 50,
              "expert_load_max": 3} for _ in range(5)]
    for name in NEW:
        for other in (olmoe, joyai):
            assert read(name)(_records(other, plain, mhc=False)) is None, name
        if name != "mhc_col_err":               # a counter: needs no trace
            assert read(name)(dict(rec, trace=None, scopes=None)) is None, \
                name
        for broken in ({"kind": "serve"}, {"kind": "train"}, {},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None),
                       dict(rec, trace={})):
            read(name)(broken)                      # and nothing raises
    # the parent's program given this model's records' shape: its ops carry
    # no `mhc` scope and its steps no counter
    bare = _records(config["model"], plain, mhc=False)
    for name in NEW:
        assert read(name)(bare) is None, name


def test_the_family_registers_its_scopes_with_the_reduction(config):
    """`mhc` and its three parts are no scopes of the harness's own list (a
    file this PR may not edit); building this family's model makes them
    scopes: an op under mhc/mhc_map is `mhc_map`'s, and nothing of the
    residual path lies under `attention` or `mlp`."""
    from benchmarks.harness import program_trace

    cfg = family.make_config(dict(config["model"], h_res_identity=True))
    assert not hasattr(cfg, "h_res_identity")   # the reference's switch alone
    family.register_scopes()                    # idempotent
    for scope in family.MHC_SCOPES:
        assert program_trace.SCOPES.count(scope) == 1
        assert program_trace.COMPUTE.count(scope) == 1
    at = "jit(_decode_fn)/jit(main)/layers/while/body/closed_call/"
    assert program_trace.scope_of(at + "mhc/mhc_map/exp") == "mhc_map"
    assert program_trace.scope_of(at + "mhc/mhc_post/mul") == "mhc_post"
    assert program_trace.scope_of(at + "mhc/concatenate") == "mhc"
    assert program_trace.scope_of(at + "proj/dot_general") == "proj"
    assert program_trace.scope_of(at + "attention/pallas_call") \
        == "attention"


# -- a tiny traced rehearsal through the sessions kind -----------------------


def _tiny_cell():
    from paddle_tpu.models import xing4

    tiny = dataclasses.asdict(xing4.Xing4Config.tiny())
    config = {
        "name": "tiny_xing4", "family": "xing4",
        "model": dict(tiny, max_len=128),
        "reduced": ["max_position_embeddings"],
        "reduced_why": {"max_position_embeddings": "8192 -> 128 (the "
                        "tests')"},
        "serve": {"precision": "f32", "block_size": 8, "decode_slots": [4],
                  "kv_context_per_slot": 128, "eos_id": None,
                  "max_queue": 64},
        "logit_gap_tol": 0.05}
    mix = {"kind": "sessions", "loop": "closed", "clients": 4,
           "table_size": 4, "context_per_slot": 4096, "weights_seed": 7,
           "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 60},
           "output_len": {"dist": "fixed", "value": 4000},
           "prefill_buckets": [32, 64], "lead_s": 4.0}
    return {"name": CELL, "chips": 1, "config_file": config,
            "traffic_file": mix}


def test_a_tiny_traced_rehearsal_is_correct_and_reports_the_new_metrics(
        tmp_path, monkeypatch, jax_cache_config):
    """Four sessions of a tiny model through the real engine, server and
    load generator: prompts walked in slices of 16 during the lead, every
    row decoding all through the window, the float32 engine's tokens the
    reference's own, every step record with the maps' counter, and the line
    with the metrics that need no device trace."""
    from tests.benchmarks.test_benchmark_program_trace import _scopes

    monkeypatch.setattr(sessions, "TRACE_S", 0.3)
    monkeypatch.setattr(sessions.program_trace, "reduce_scopes",
                        lambda path: _scopes())
    args = types.SimpleNamespace(seed=2 ** 31 + 45, seconds=2.0, trace=1,
                                 rate=None, t_start=time.monotonic(),
                                 workload=CELL)
    res = sessions.run(copy.deepcopy(_tiny_cell()), args, str(tmp_path),
                       allow_cpu=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert (res["attempted"], res["failed"]) == (4, 0)
    assert checks["compared"]["window_admissions"] == [0, 0]
    assert checks["compared"]["ref_max_logit_gap"][0] <= 0.05
    mem = checks["memory"]
    assert mem["kv_bytes_per_token"] == 4 * (32 + 128) * 2
    steps = [s for s in res["records"]["program"]["steps"]
             if s["kind"] == "decode"]
    assert steps and all(0.0 <= s["mhc_col_err"] < 0.05
                         and s["experts_hit"] >= 2 for s in steps)
    bench = manifest.load_manifest()
    line = json.loads(json.dumps(bench_run.emit(bench, args, res)))
    got = line["metrics"]
    assert got["mhc_col_err"]["unit"] == "ratio"
    assert got["mhc_col_err"]["value"] == max(s["mhc_col_err"]
                                              for s in steps)
    assert got["window_admissions.xing4"]["value"] == 0.0
    assert got["sessions_ready_s.xing4"]["value"] > 0
    assert got["expert_load_max_over_mean.xing4"]["value"] >= 1.0
    assert {"stream_silence_share.xing4", "slot_occupancy",
            "engine_step_p50_ms.tput", "kv_block_used_share.tput",
            "setup_engine_warm_s.sessions", "setup_compile_s"} <= set(got)
    # the stand-in reduction has no `mhc` seconds: the trace's readers
    # leave their metrics out
    assert not {"mhc_share", "mhc_roofline"} & set(got)


def test_the_controls_read_their_faults_at_a_tiny_size():
    """`xing4_control.readings` as the chip runs it, at a tiny size in
    float32: a switch moves the faulty reference's picks off the right
    one's, and rounding the matrices to float8 moves them further than
    rounding them to bf16."""
    from paddle_tpu.models import xing4
    from tests.benchmarks import xing4_control

    cfg = xing4.Xing4Config.tiny()
    model = dataclasses.asdict(cfg)
    rng = np.random.default_rng(3)
    sequences = [rng.integers(0, cfg.vocab_size, n).tolist()
                 for n in (24, 40)]
    got = xing4_control.readings(
        lambda: family.init(family.make_config(model), 7)[0], model,
        sequences, 8, ["h_res_identity", "float8", "bf16"])
    assert got["program"] > 0.0 and 0 <= got["exact"] <= 16   # random picks
    assert got["h_res_identity"] > 0.0
    assert got["float8"] > got["bf16"] >= 0.0
    assert set(xing4_control.SWITCHES) <= set(family.REFERENCE_SWITCHES)
