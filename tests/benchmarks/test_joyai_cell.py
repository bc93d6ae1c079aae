"""The cell `joyai_llm_flash.rag_closed` off the chip: its configuration
file against its source's keys, its byte counts against the program's
shapes, its three readers on records made by hand (and on a program without
a latent cache or expert counters: nothing, and no error), a tiny rehearsal
through the serve kind, and what `logit_gap_tol` tells apart at the
published widths (the cell's 5 layers, 16 of the 256 experts and an eighth
of the vocabulary, for the CPU)."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks.families import joyai as joyai_family
from benchmarks.harness import joyai_shapes, manifest, traffic
from benchmarks.reference import joyai_ref

CELL = "joyai_llm_flash.rag_closed"
SEED = 3000000019


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "joyai_llm_flash.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the source's config.json stands at the top level under
    its own name; `model` repeats the sizes under the program's names, and
    only the keys under `reduced` differ from the source."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"])
    assert config["num_hidden_layers"] == 5
    assert config["max_position_embeddings"] == 4608
    assert config["num_nextn_predict_layers"] == 0      # the MTP module
    assert "mtp" in config["not_served"]
    for ours, theirs in config["source_keys"].items():
        assert config["model"][ours] == config[theirs], ours
    assert set(config["assumed"]) >= {"init", "router_bias", "rope"}
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "joyai_llm_flash")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # no width is cut: experts, experts a token and the vocabulary are whole
    assert not [k for k in config["reduced"]
                if k.endswith(("_size", "_dim", "_rank", "_heads", "_tok",
                               "_experts"))]
    assert config["logit_gap_tol_reason"] != "TO BE MEASURED"


def test_the_cell_is_found_with_its_readers():
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    assert cell["chips"] == 1 and mix["clients"] == 32
    assert mix["prefill_buckets"] == [1024, 2048, 3072, 4096]
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 1024,
                                 "hi": 4096}
    assert mix["output_len"] == {"dist": "uniform", "lo": 128, "hi": 512}
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name, layer in (("latent_attention_roofline", "decode kernels"),
                        ("latent_attention_share", "decode kernels"),
                        ("expert_layer_roofline", "expert layer")):
        assert per_layer[name]["layer"] == layer
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert per_layer[name]["workloads"] == [CELL]
        assert manifest.layer_metric_reader(name) is not None
    # `moe_share` and `expert_load_max_over_mean` are read for this cell by
    # the reader files the benchmark had, under entries of its own: the
    # accepted entries' lists are pinned to OLMoE's cell by a test that a
    # later PR may not edit (tests/benchmarks/test_olmoe_cell.py)
    for name in ("moe_share.joyai", "expert_load_max_over_mean.joyai"):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "expert layer"
        assert manifest.layer_metric_reader(name) is not None
    assert not {"moe_share", "expert_load_max_over_mean"} & set(per_layer)
    assert {"engine_step_p50_ms.tput", "decode_step_roofline.tput",
            "decode_compute_share.tput", "slot_occupancy",
            "engine_prefill_share.tput", "stream_gap_p95_ms",
            "hbm_planned_share.serve_tput"} <= set(per_layer)
    assert "moe_layer_roofline" not in per_layer    # OLMoE's shapes
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    # the longest sequence of the mix fits a slot's context, and every
    # prompt a bucket
    serve = cell["config_file"]["serve"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= serve["kv_context_per_slot"] == cell["config_file"]["model"][
            "max_len"] == 4608
    assert mix["prompt_len"]["hi"] <= max(mix["prefill_buckets"])
    assert serve["decode_slots"] == [32] and serve["block_size"] == 16


def test_the_byte_counts_follow_the_programs_shapes(config):
    import jax

    from paddle_tpu.models import joyai

    model = config["model"]
    cfg = joyai_family.make_config(model)
    shapes = jax.eval_shape(lambda k: joyai.init(k, cfg)[0],
                            jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert joyai_shapes.param_count(model) == n
    assert 2 * n == pytest.approx(11.12e9, rel=0.002)   # bf16 weights
    assert joyai_shapes.expert_bytes(model) == 9437184
    assert joyai_shapes.expected_experts_hit(model, 32) \
        == pytest.approx(163.3, abs=0.1)
    # what a token holds of the pools, as the engine's own geometry says
    sm = cfg.serve_model()
    assert joyai_family.kv_bytes_per_token(model) == 5 * 1280 \
        == model["layers"] * sum(sm.stored) * 2
    assert joyai_shapes.kv_content_bytes_per_token(model) == 5 * 1152
    # a 32-row step with 80000 tokens resident: 7.5 GB, the routed experts
    # 6.2 of them, the cache 0.46
    least = joyai_family.decode_step_min_bytes(model, 80000.0)
    experts = 4 * joyai_shapes.expected_experts_hit(model, 32) * 9437184
    assert least == pytest.approx(7.52e9, rel=0.01)
    assert experts / least == pytest.approx(0.82, abs=0.01)
    assert 80000 * 5 * 1152 / least == pytest.approx(0.061, abs=0.003)
    assert joyai_shapes.mlp_min_bytes(model, 650.0) == pytest.approx(
        3 * 2048 * 7168 * 2 + 4 * (2048 * 256 + 256 + 3 * 2048 * 768) * 2
        + 650 * 9437184)
    # the absorbed attention: 60 FLOP a byte, under the v5e's ridge of 240
    flops = joyai_shapes.latent_attention_flops(model, 80000.0)
    moved = joyai_shapes.latent_attention_min_bytes(model, 80000.0)
    assert 50 < flops / moved < 70 < 197e12 / 819e9


# -- the readers -------------------------------------------------------------


def _records(model, steps, live=80000.0):
    decode = "jit__decode_fn"
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"live_tokens_mean": live,
                  "modules": {decode: {"count": 100, "median_s": 0.012},
                              "jit__prefill_fn": {"count": 3,
                                                  "median_s": 0.3}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 1.2, "by_scope": {
                "mlp": 0.9, "attention": 0.12, "qkv": 0.06, "head": 0.06,
                "layers.other": 0.06}},
            "jit__prefill_fn": {"total_s": 0.9,
                                "by_scope": {"mlp": 0.5, "attention": 0.3}}}},
        "program": {"steps": steps}}


def test_the_three_readers_on_records_made_by_hand(config):
    model = config["model"]
    steps = [{"kind": "decode", "slots": 32, "experts_hit": 640 + 2 * i,
              "expert_load_max": 5 + i % 3} for i in range(11)]
    steps.append({"kind": "prefill", "slots": 1})
    rec = _records(model, steps)
    read = manifest.layer_metric_reader
    assert read("latent_attention_share")(rec) == pytest.approx(0.1)
    # 80000 tokens x 5760 B + 5 x 8.4 MB of W_kvb = 0.503 GB: 0.614 ms at
    # 819 GB/s, against 0.12 s / 100 steps = 1.2 ms under `attention`
    assert read("latent_attention_roofline")(rec) == pytest.approx(
        100 * (80000 * 5760 + 5 * 512 * 32 * 256 * 2) / 819e9 / 0.0012)
    assert 40 < read("latent_attention_roofline")(rec) < 60
    # 650 experts x 9.44 MB + the dense layer + 4 x (router + shared) =
    # 6.26 GB: 7.65 ms, against 0.9 s / 100 steps = 9 ms under `mlp`
    assert read("expert_layer_roofline")(rec) == pytest.approx(
        100 * joyai_shapes.mlp_min_bytes(model, 650.0) / 819e9 / 0.009)
    assert 80 < read("expert_layer_roofline")(rec) < 90
    # the readers the benchmark had serve this model too
    assert read("moe_share.joyai")(rec) == pytest.approx(0.75)
    assert read("expert_load_max_over_mean.joyai")(rec) == pytest.approx(
        np.mean([5 + i % 3 for i in range(11)]) / 1.0)


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """The parent's program, GPT-2's or OLMoE's: no latent cache in the
    model group, no leading dense layer, step records without counters, no
    trace: the metric is left out, nothing raises."""
    read = manifest.layer_metric_reader
    names = ("latent_attention_roofline", "latent_attention_share",
             "expert_layer_roofline")
    steps = [{"kind": "decode", "slots": 32, "experts_hit": 650,
              "expert_load_max": 5} for _ in range(5)]
    rec = _records(config["model"], steps)
    plain = [{"kind": "decode", "slots": 32} for _ in range(5)]
    assert read("expert_layer_roofline")(_records(config["model"],
                                                  plain)) is None
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    for name in names:
        assert read(name)(_records(olmoe, steps)) is None, name
        assert read(name)(dict(rec, trace=None)) is None, name   # untraced
        for broken in ({"kind": "serve"}, {"kind": "train"},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None)):
            read(name)(broken)                      # and nothing raises
    no_live = dict(rec, trace={k: v for k, v in rec["trace"].items()
                               if k != "live_tokens_mean"})
    assert read("latent_attention_roofline")(no_live) is None
    assert read("expert_layer_roofline")(dict(rec, program=None)) is None
    assert read("expert_layer_roofline")(dict(rec, peaks=None)) is None


# -- a tiny rehearsal through the serve kind ----------------------------------

TINY_JOYAI = {
    "family": "joyai",
    "model": {"vocab_size": 512, "hidden": 64, "layers": 3,
              "dense_layers": 1, "heads": 4, "q_rank": 48, "kv_rank": 32,
              "nope_dim": 16, "rope_dim": 8, "v_dim": 16, "dense_dim": 96,
              "expert_dim": 32, "n_experts": 8, "top_k": 2,
              "route_scale": 2.5, "max_len": 128, "rope_theta": 32e6,
              "rms_eps": 1e-6, "dtype": "bfloat16"},
    "serve": {"precision": "bf16", "block_size": 16, "decode_slots": [4],
              "kv_context_per_slot": 128, "eos_id": None, "max_queue": 64},
    "logit_gap_tol": 0.5}
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


def test_tiny_rag_closed_rehearsal(tmp_path, jax_cache_config):
    from benchmarks.kinds import serve

    cell = {"name": "tiny.rag_closed", "chips": 1,
            "config_file": TINY_JOYAI, "traffic_file": TINY_MIX}
    args = types.SimpleNamespace(seed=2 ** 31 + 19, seconds=2.0, trace=0,
                                 rate=None, t_start=time.monotonic())
    res = serve.run(cell, args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["checks"]["compiles_in_window"] == 0
    assert res["checks"]["ref_tokens"] == 64
    mem = res["checks"]["memory"]
    # the pools hold the latent and the rotary key's lane tile, 3 layers
    assert mem["kv_bytes_per_token"] == 3 * (32 + 128) * 2
    assert mem["kv_pool_bytes"] == (4 * 8 + 1) * 16 * mem["kv_bytes_per_token"]
    base = mem["resident_at_start"]
    assert mem["resident_bytes"] - base \
        <= mem["weight_bytes"] + mem["kv_pool_bytes"] + mem["weight_bytes"] // 4
    assert mem["resident_dropped"] - base <= mem["weight_bytes"] // 4


def test_the_served_set_is_the_float32_one_rounded_once():
    import jax.numpy as jnp

    cfg = joyai_family.make_config(TINY_JOYAI["model"])
    served, axes = joyai_family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = joyai_family.init(cfg, SEED)
    assert axes["blk.w_gate"] == ("layer", "expert", "embed", "mlp")
    assert axes["dense.mlp_gate"] == ("layer", "embed", "mlp")
    assert set(axes) == set(served)
    for k, v in f32.top.items():
        assert v.dtype == jnp.float32 and served[k].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(v.astype(jnp.bfloat16)),
                              np.asarray(served[k])), k
    for i in range(cfg.layers):
        dense = i < cfg.dense_layers
        layer = f32.layer(i)
        assert ("blk.mlp_gate" in layer) == dense
        assert ("blk.router" in layer) == (not dense)
        for k, v in layer.items():
            stack = served["dense." + k[4:]][i] if dense \
                else served[k][i - cfg.dense_layers]
            # to the last bit but one: XLA may fold an init scale another
            # way in the program that makes every layer
            got = np.asarray(stack.astype(jnp.float32))
            want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
            assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
            assert (got != want).mean() < 1e-3, k


# -- what the tolerance tells apart, at the published widths -----------------

T = 160
CUT = {"layers": 5, "n_experts": 16, "vocab_size": 16160}


@pytest.fixture(scope="module")
def published(config):
    """The published widths and the cell's depth (1 dense + 4 expert
    layers) with the expert count (16 of 256; still top-8) and the
    vocabulary (an eighth) cut for the CPU, one sequence of 160 seeded
    tokens: the PROGRAM's pick at every position (its full forward pass in
    bf16 from the served set; a decode step makes the same pick from the
    same prefix, tests/test_joyai.py) is judged as the serve kind judges a streamed token: how
    far it lies, in the reference's float32 logits, below the reference's
    own argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import joyai

    model = dict(config["model"], **CUT)
    cfg = joyai_family.make_config(model)
    served, _ = joyai_family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = joyai_family.init(cfg, SEED)
    ids = jnp.asarray(traffic.prompt_ids(SEED, 0, T, model["vocab_size"]),
                      jnp.int32)
    picks = np.asarray(jax.jit(lambda p, i: joyai.apply(p, cfg, i))(
        served, ids[None])[0].argmax(-1))
    return config, model, f32, ids, picks


def _gap(published, model=None, weights=None):
    """`weights(name, value)`: a control on the reference's parameters."""
    import jax

    config, right, f32, ids, picks = published
    model = model or right
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, v) for k, v in f32.top.items()}
    step = jax.jit(lambda lp, x: joyai_ref.block(lp, x, model))
    with jax.default_matmul_precision("highest"):
        x = top["wte.w"][ids]
        for i in range(model["layers"]):
            x = step({k: weights(k, v) for k, v in f32.layer(i).items()}, x)
        rows = np.asarray(joyai_ref.head_rows(top, model, x, 0, T))
    return joyai_ref.verdict(rows.max(-1) - rows[np.arange(T), picks])


def test_the_bf16_program_is_within_the_tolerance(published):
    assert _gap(published) <= published[0]["logit_gap_tol"] / 2


@pytest.mark.parametrize("fault, switch", [
    ("shared_expert_dropped", {"shared_expert": False}),
    ("unnormalised_weights", {"norm_topk_prob": False}),
    ("scale_2.5_left_out", {"route_scale": 1.0}),
    ("rotate_half_for_interleaved", {"rope": "half"}),
    ("rope_over_the_wrong_64", {"rope_on": "nope"}),
    ("latent_cached_before_its_norm", {"kv_norm": False})])
def test_the_tolerance_fails_a_fault(published, fault, switch):
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], **switch)) > tol, fault


def test_float8_weights_are_not_correct(published):
    """The nearest precision below the stated one: the reference with
    its matrices rounded to float8 (e4m3) is over the tolerance, the same
    matrices rounded to bf16, which is what the program serves, under it."""
    import jax.numpy as jnp

    def rounded(dtype):
        return lambda k, v: v.astype(dtype).astype(jnp.float32) \
            if v.ndim >= 2 else v

    tol = published[0]["logit_gap_tol"]
    assert _gap(published, weights=rounded(jnp.float8_e4m3fn)) > tol
    assert _gap(published, weights=rounded(jnp.bfloat16)) <= tol / 2


ROUTED = ("blk.w_gate", "blk.w_up", "blk.w_down")


def test_the_routed_experts_stand_at_the_plain_scale(published):
    """The routed experts' part of a layer's output is the largest: their
    matrices keep the deviation every other matrix of the layer has (no
    factor mutes them), and what the layer's experts share adds up over a
    token's eight. With the routed experts left out the reference is
    farther from the program than with the shared expert left out."""
    import jax.numpy as jnp

    config, model, f32, _, _ = published
    lp = f32.layer(model["dense_layers"])
    assert abs(float(lp["blk.w_down"].std() / lp["blk.shared_down"].std())
               - 1.0) < 0.02
    assert abs(float(lp["blk.w_gate"].std() / lp["blk.shared_gate"].std())
               - 1.0) < 0.02
    without = _gap(published, weights=lambda k, v: jnp.zeros_like(v)
                   if k == "blk.w_down" else v)
    assert without > _gap(published, dict(model, shared_expert=False))
    assert without > 4 * config["logit_gap_tol"]


def test_every_token_given_other_experts_is_not_correct(published):
    """WHICH experts a token is given still counts: with every expert's
    matrices moved half way round (each token computes with eight experts
    it did not choose) the reference is over the tolerance."""
    import jax.numpy as jnp

    tol = published[0]["logit_gap_tol"]
    half = published[1]["n_experts"] // 2
    assert _gap(published, weights=lambda k, v: jnp.roll(v, half, axis=0)
                if k in ROUTED else v) > tol
