"""The cell `longcat_flash_chat.chat_closed` off the chip: its configuration
file against the catalog row's keys, its byte counts against the program's
shapes and against ISSUE 52's arithmetic, its new readers on records made
by hand (and on another family's records or the parent's: nothing, and no
error), the traffic file's parameters as the issue names them, a tiny
rehearsal through the serve kind with the controls' script on its files.
What `logit_gap_tol` tells apart at the published widths is read on the
chip (`tests/benchmarks/longcat_control.py`; the configuration file has
the readings): one float32 layer of this model is 5 GB, more than a test
run here may hold."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks.families import longcat as family
from benchmarks.harness import longcat_shapes as shapes, manifest, traffic
from benchmarks.reference import longcat_ref as ref_mod

CELL = "longcat_flash_chat.chat_closed"
SEED = 3000000052


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "longcat_flash_chat.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the catalog row's `config` stands at the top level
    under its own name; `model` repeats the sizes under the program's
    names, and only the keys under `reduced` differ from the source."""
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"]) \
        == {"num_layers", "n_routed_experts", "vocab_size",
            "max_position_embeddings"}
    model = config["model"]
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"], config["max_position_embeddings"]) \
        == (4, 16, 16384, 1024)
    # the router keeps its published width: the key counts the experts HELD
    assert model["n_experts"] == published["n_routed_experts"] == 512
    assert model["held"] == [0, config["n_routed_experts"]]
    assert model["n_experts"] + model["zero_experts"] == 768
    for ours, theirs in config["source_keys"].items():
        if theirs in config:
            assert model[ours] == config[theirs], ours
    assert set(config["assumed"]) >= {"layer", "router", "mlp", "rope",
                                      "softmax_scale", "mla_scales", "init",
                                      "router_bias"}
    assert "one of 32 chips that share each layer" in config["deployment"]
    assert "32 times their share" in config["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "longcat_flash_chat")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/longcat_flash_chat.json"
    assert config["logit_gap_tol_reason"] != "TO BE FILLED FROM CHIP READINGS"
    cfg = family.make_config(model)
    assert cfg.routing.held_range == (0, 16) and cfg.routing.partial
    assert cfg.serve_model().kv_layers == 8


def test_the_cell_is_found_with_its_readers():
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    # ISSUE 52's traffic: `chat_closed`, unedited
    assert cell["chips"] == 1 and mix["kind"] == "serve"
    assert mix["loop"] == "closed" and mix["clients"] == 128
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 32, "hi": 512}
    assert mix["output_len"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert mix["prefill_buckets"] == [64, 128, 256, 512]
    assert mix["table_size"] == 512 and mix["lead_s"] == 20
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name, layer in (("shortcut_expert_share", "expert layer"),
                        ("shortcut_expert_roofline", "expert layer"),
                        ("scmoe_dense_roofline", "models and XLA kernels"),
                        ("latent64_attention_roofline", "decode kernels"),
                        ("held_pair_share", "expert layer"),
                        ("zero_pair_share", "expert layer"),
                        ("held_expert_load_max_over_mean", "expert layer")):
        assert per_layer[name]["layer"] == layer
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert per_layer[name]["workloads"] == [CELL]
        assert manifest.layer_metric_reader(name) is not None
    assert {"engine_step_p50_ms.tput", "decode_step_roofline.tput",
            "decode_compute_share.tput", "slot_occupancy",
            "engine_prefill_share.tput", "engine_host_share.tput",
            "prefill_gap_share.tput", "stream_gap_p95_ms",
            "kv_block_used_share.tput", "device_idle_share.serve_tput",
            "hbm_planned_share.serve_tput", "setup_first_program_s",
            "setup_compile_s", "setup_lower_s", "setup_cache_misses",
            "setup_engine_warm_s"} <= set(per_layer)
    # the readers keyed on another family's model find no cell here
    assert not {"moe_share", "expert_layer_roofline", "dense_mlp_roofline",
                "latent_attention_roofline", "latent_attention_share",
                "expert_load_max_over_mean"} & set(per_layer)
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    serve = cell["config_file"]["serve"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= serve["kv_context_per_slot"] == cell["config_file"]["model"][
            "max_len"] == 1024
    assert serve["decode_slots"] == [128] == [mix["clients"]]
    assert serve["block_size"] == 16 and serve["precision"] == "bf16"
    assert serve["max_queue"] == 512
    # where the byte counts' slot count comes from (families/longcat.py)
    import inspect

    assert inspect.signature(shapes.decode_step_min_bytes).parameters[
        "slots"].default == max(serve["decode_slots"])


def test_the_byte_counts_follow_the_programs_shapes(config):
    import jax

    from paddle_tpu.models import longcat

    model = config["model"]
    cfg = family.make_config(model)
    made = jax.eval_shape(lambda k: longcat.init(k, cfg)[0],
                          jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in made.values())
    assert shapes.param_count(model) == n
    # ISSUE 52's arithmetic (it counts the matrices; the norms' gains and
    # the correction bias are the 0.1 M between)
    assert n == pytest.approx(5172.6e6, rel=1e-4)
    assert 2 * n == pytest.approx(10.35e9, rel=1e-3)
    assert shapes.attention_params(model) == pytest.approx(90.57e6, rel=1e-4)
    assert shapes.dense_mlp_params(model) == pytest.approx(226.5e6, rel=1e-4)
    assert shapes.router_params(model) == pytest.approx(4.72e6, rel=1e-3)
    assert shapes.outside_experts_params(model) == pytest.approx(
        638.9e6, rel=1e-4)
    assert shapes.expert_bytes(model) == pytest.approx(75.5e6, rel=1e-3)
    assert shapes.held_experts(model) == 16
    assert shapes.layer_params(model) == pytest.approx(1242.8e6, rel=1e-4)
    assert 2 * model["vocab_size"] * model["hidden"] == pytest.approx(
        201.3e6, rel=1e-3)
    # all 512 experts of a layer: no chip holds one whole layer
    whole = dict(model, held=None)
    assert shapes.held_experts(whole) * shapes.expert_bytes(whole) \
        == pytest.approx(38.7e9, rel=2e-3)
    # a tiny model's arrays, the router's bias and the norms' gains too
    tiny = longcat.LongcatConfig.tiny()
    import dataclasses

    made = jax.eval_shape(lambda k: longcat.init(k, tiny)[0],
                          jax.random.key(0))
    assert shapes.param_count(dataclasses.asdict(tiny)) == sum(
        int(np.prod(v.shape)) for v in made.values())
    # what a token holds, as the engine's own geometry says
    sm = cfg.serve_model()
    assert family.kv_bytes_per_token(model) == 10240 \
        == sm.kv_layers * sum(sm.stored) * 2
    assert 128 * 1024 * 10240 == pytest.approx(1.34e9, rel=2e-3)
    # a 128-row step at 600 tokens a slot: 10.3 GB as the issue reckons it
    # (outside the experts 5.11, the head 0.20, 55.5 experts 4.19, cache)
    assert 4 * shapes.expected_experts_hit(model, 128) == pytest.approx(
        55.5, abs=0.1)
    least = family.decode_step_min_bytes(model, 128 * 600.0)
    assert least == pytest.approx(10.2e9, rel=1e-2)
    assert least == shapes.always_read_bytes(model) \
        + 4 * shapes.expected_experts_hit(model, 128) \
        * shapes.expert_bytes(model) + 128 * 600 * 8 * 1152
    assert shapes.dense_mlp_min_bytes(model) == pytest.approx(3.62e9,
                                                              rel=2e-3)
    assert shapes.shortcut_min_bytes(model, 55.5) == pytest.approx(
        4.23e9, rel=2e-3)
    # the absorbed walk at 64 heads: 121 FLOP a byte, memory still binds
    flops = shapes.latent_attention_flops(model, 76800.0)
    moved = shapes.latent_attention_min_bytes(model, 76800.0)
    assert moved / 819e9 > flops / 197e12
    assert 2 * 64 * (576 + 512) / 1152 == pytest.approx(121, abs=1)


def test_the_step_count_takes_the_expected_experts_of_a_uniform_router(
        config):
    """One path, the sibling families': the expectation; the records' own
    count is `shortcut_expert_roofline`'s to read."""
    model = config["model"]
    expected = 4 * 16 * (1 - (63 / 64) ** 128)          # 55.5 of 64
    assert 4 * shapes.expected_experts_hit(model, 128) == \
        pytest.approx(expected)
    least = family.decode_step_min_bytes(model, 1000.0)
    assert least == shapes.decode_step_min_bytes(model, 1000.0)
    assert least == pytest.approx(shapes.decode_step_min_bytes(
        model, 1000.0, experts_hit=expected))


# -- the readers -------------------------------------------------------------

NEW = ("shortcut_expert_share", "shortcut_expert_roofline",
       "scmoe_dense_roofline", "latent64_attention_roofline",
       "held_pair_share", "zero_pair_share",
       "held_expert_load_max_over_mean")
COUNTERS = NEW[4:]


def _records(model, steps, live=76800.0):
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": live,
                  "modules": {decode: {"count": 100, "median_s": 0.016},
                              prefill: {"count": 40, "median_s": 0.02}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 1.6, "by_scope": {
                "mlp": 0.55, "shortcut_experts": 0.6, "attention": 0.15,
                "qkv": 0.15, "proj": 0.08, "head": 0.03,
                "layers.other": 0.04}},
            prefill: {"total_s": 0.8, "by_scope": {
                "mlp": 0.4, "shortcut_experts": 0.2, "attention": 0.05}}}},
        "program": {"steps": steps}}


def _steps():
    steps = [{"kind": "decode", "slots": 128, "pairs": 128 * 12 * 4,
              "held_pairs": 120 + i, "zero_pairs": 2040 + i,
              "experts_hit": 54 + i % 3, "expert_load_max": 6 + i % 2}
             for i in range(10)]
    steps += [{"kind": "prefill", "slots": 1, "live_tokens": n}
              for n in (40, 173, 306)]
    return steps


def test_the_new_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    assert read("shortcut_expert_share")(rec) == pytest.approx(0.6 / 1.6)
    # 4 routers + 54.9 experts x 75.5 MB = 4.16 GB: 5.08 ms at 819 GB/s,
    # against 0.6 s / 100 steps = 6 ms under `shortcut_experts`
    hit = np.mean([s["experts_hit"] for s in _steps()[:10]])
    assert read("shortcut_expert_roofline")(rec) == pytest.approx(
        100 * shapes.shortcut_min_bytes(model, hit) / 819e9 / 0.006)
    assert 80 < read("shortcut_expert_roofline")(rec) < 90
    # 3.62 GB: 4.42 ms, against 5.5 ms under `mlp`
    assert read("scmoe_dense_roofline")(rec) == pytest.approx(
        100 * 3.624e9 / 819e9 / 0.0055, rel=1e-3)
    # 76800 tokens x 8 x 1152 B + 8 x W_kvb: 0.84 GB, 1.03 ms, against
    # 1.5 ms under `attention`; the operations take 0.48 ms
    want = shapes.latent_attention_min_bytes(model, 76800.0) / 819e9
    assert want > shapes.latent_attention_flops(model, 76800.0) / 197e12
    assert read("latent64_attention_roofline")(rec) == pytest.approx(
        100 * want / 0.0015)
    assert read("held_pair_share")(rec) == pytest.approx(124.5 / 6144)
    assert read("zero_pair_share")(rec) == pytest.approx(2044.5 / 6144)
    assert read("held_expert_load_max_over_mean")(rec) == pytest.approx(
        np.mean([s["expert_load_max"] / (s["held_pairs"] / 64)
                 for s in _steps()[:10]]))
    # the shared reader serves this model through the family's byte count
    assert read("decode_step_roofline.tput")(dict(rec, trace=dict(
        rec["trace"], decode_min_bytes=10.3e9))) == pytest.approx(
        100 * 10.3e9 / 819e9 / 0.016, rel=1e-3)
    assert all(read(n)(rec) <= 100 for n in NEW[1:4])


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """Another family's records (JoyAI's, OLMoE's), the parent's program
    (no `shortcut_experts` scope on any op, no such counters, no trace):
    the metric is left out, nothing raises; and the older families'
    readers give nothing for this one."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "joyai_llm_flash.json")) as f:
        joyai = json.load(f)["model"]
    plain = [{"kind": "decode", "slots": 16, "experts_hit": 40,
              "expert_load_max": 5} for _ in range(5)]
    for name in NEW:
        assert read(name)(_records(olmoe, plain)) is None, name
        assert read(name)(_records(joyai, plain)) is None, name
        if name not in COUNTERS:        # a counter needs no trace
            assert read(name)(dict(rec, trace=None, scopes=None)) is None, \
                name
        # the parent's program serving this model: no such counter
        assert read(name)(_records(config["model"], plain)
                          if name in COUNTERS else
                          dict(rec, scopes=None)) is None, name
        for broken in ({"kind": "serve"}, {"kind": "train"},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None)):
            read(name)(broken)                      # and nothing raises
    bare = _records(config["model"], _steps())
    for prog in bare["scopes"]["programs"].values():
        del prog["by_scope"]["shortcut_experts"]
    for name in NEW[:2]:
        assert read(name)(bare) is None, name
    for theirs in ("moe_share", "moe_layer_roofline", "expert_layer_roofline",
                   "dense_mlp_roofline", "selective_ssm_share",
                   "relu2_expert_roofline"):
        read(theirs)(rec)       # may read the shared scopes; never raises
    for theirs in ("dense_mlp_roofline", "selective_ssm_share",
                   "selective_update_roofline", "mhc_share"):
        assert read(theirs)(rec) is None, theirs


def test_the_family_registers_its_scope_with_the_reduction(config):
    from benchmarks.harness import program_trace

    cfg = family.make_config(dict(config["model"], zero_term=False))
    assert not hasattr(cfg, "zero_term")        # the reference's switch
    family.register_scopes()                        # idempotent
    assert program_trace.SCOPES.count(family.SCOPE) == 1
    assert program_trace.COMPUTE.count(family.SCOPE) == 1
    base = "jit(_decode_fn)/jit(main)/layers/while/body/closed_call/"
    assert program_trace.scope_of(
        base + "shortcut_experts/experts/jit(gmm)/pallas_call") \
        == family.SCOPE
    assert program_trace.scope_of(
        base + "shortcut_experts/zero_experts/mul") == family.SCOPE
    assert program_trace.scope_of(base + "mlp/dense_mlp/dot_general") == "mlp"
    assert family.is_longcat({"model": config["model"]})
    assert not family.is_longcat({"model": {"n_experts": 64}})


# -- a tiny rehearsal through the serve kind ----------------------------------

TINY = {
    "family": "longcat",
    "model": {"vocab_size": 512, "hidden": 64, "layers": 2, "heads": 4,
              "q_rank": 48, "kv_rank": 32, "nope_dim": 16, "rope_dim": 8,
              "v_dim": 16, "dense_dim": 96, "expert_dim": 32,
              "n_experts": 8, "zero_experts": 4, "top_k": 3,
              "route_scale": 6.0, "held": [2, 4], "max_len": 128,
              "rope_theta": 1e7, "rms_eps": 1e-5, "dtype": "bfloat16"},
    # float32: among 12 outputs a top-3 pick weighs 6 x 0.15 and bf16
    # rounding flips one in thirty, each worth 2 logits at this size (the
    # cell's 768 outputs make a pick worth 0.04); what is rehearsed here is
    # the path, not the rounding
    "serve": {"precision": "f32", "block_size": 16, "decode_slots": [4],
              "kv_context_per_slot": 128, "eos_id": None, "max_queue": 64},
    "logit_gap_tol": 0.01}
TINY_MIX = {"kind": "serve", "loop": "closed", "clients": 4,
            "table_size": 24,
            "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 60},
            "output_len": {"dist": "uniform", "lo": 16, "hi": 40},
            "prefill_buckets": [16, 32, 64], "lead_s": 0.5}


@pytest.fixture
def jax_cache_config():
    """The serve kind places JAX's persistent cache for its process
    (`device.place_cache`); the test gives the settings back, so that the
    tests that follow it in this worker compile as tier-1 does: cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_tiny_chat_closed_rehearsal(tmp_path, jax_cache_config):
    from benchmarks.kinds import serve
    from tests.benchmarks import longcat_control

    cell = {"name": "tiny.chat_closed", "chips": 1,
            "config_file": TINY, "traffic_file": TINY_MIX}
    args = types.SimpleNamespace(seed=2 ** 31 + 52, seconds=2.0, trace=0,
                                 rate=None, t_start=time.monotonic())
    res = serve.run(cell, args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["checks"]["compiles_in_window"] == 0
    assert res["checks"]["ref_tokens"] == 64
    mem = res["checks"]["memory"]
    # four cache layers for two layers: 32 + 128 lanes each (the family's
    # count is of the served bf16)
    assert mem["kv_bytes_per_token"] == 4 * (32 + 128) * 2
    assert mem["kv_pool_bytes"] == (4 * 8 + 1) * 16 * 4 * (32 + 128) * 4
    # the controls as the chip runs them, on this run's own files: the
    # served streams are within rounding, a fault is not
    with open(os.path.join(str(tmp_path), "requests.jsonl")) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    sample = longcat_control.sample_of(requests, args.seed)
    assert [r["idx"] for r in sample] == res["checks"]["sampled"]
    model = TINY["model"]
    cfg = family.make_config(model)
    got = longcat_control.readings(
        lambda: family.init(cfg, args.seed)[0], model,
        [traffic.prompt_ids(args.seed, r["idx"], r["prompt_len"], 512)
         for r in sample], [r["tokens"][:16] for r in sample],
        ["zero_term", "shortcut_early", "held_term"])
    assert got["program"] == pytest.approx(
        res["checks"]["ref_max_logit_gap"], abs=1e-5)
    for fault in ("zero_term", "shortcut_early", "held_term"):
        assert got[fault] > 10 * TINY["logit_gap_tol"], got
    assert set(s for f in longcat_control.SWITCHES.values() for s in f) \
        <= set(family.REFERENCE_SWITCHES)


def test_the_served_set_is_the_float32_one_rounded_once():
    import jax.numpy as jnp

    cfg = family.make_config(TINY["model"])
    served, axes = family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = family.init(cfg, SEED)
    assert set(axes) == set(served)
    for k, v in f32.top.items():
        assert v.dtype == jnp.float32 and served[k].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(v.astype(jnp.bfloat16)),
                              np.asarray(served[k])), k
    for i in range(cfg.layers):
        layer = f32.layer(i)
        assert layer["blk.w_up"].shape == (2, 64, 32)      # the HELD ones
        assert layer["blk.router"].shape == (64, 12)
        assert set(layer) == {k for k in served if k.startswith("blk.")}
        for k, v in layer.items():
            got = np.asarray(served[k][i].astype(jnp.float32))
            want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
            assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
            assert (got != want).mean() < 1e-3, k
    # the reference walks the same layers the program stacks
    assert set(ref_mod.layer_of(served, None, 0)) == set(f32.layer(0))
