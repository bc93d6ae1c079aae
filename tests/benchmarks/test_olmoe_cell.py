"""The cell `olmoe_1b_7b.gen_closed` off the chip: its configuration file
against its source's keys, its byte counts against the program's shapes,
its three readers on records made by hand (and on a program that counts no
experts: nothing, and no error), a tiny rehearsal through the serve kind,
and what `logit_gap_tol` tells apart at the published widths (2 layers of
the 8, on the CPU)."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks.families import olmoe as olmoe_family
from benchmarks.harness import manifest, olmoe_shapes, traffic
from benchmarks.reference import olmoe_ref

CELL = "olmoe_1b_7b.gen_closed"
SEED = 3000000011


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "olmoe_1b_7b.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the source's config.json stands at the top level under
    its own name; `model` repeats the sizes under the program's names, and
    only the keys under `reduced` differ from the source."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"])
    assert config["num_hidden_layers"] == 8
    assert config["max_position_embeddings"] == 1024
    for ours, theirs in config["source_keys"].items():
        assert config["model"][ours] == config[theirs], ours
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "olmoe_1b_7b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # no width is cut
    assert not [k for k in config["reduced"]
                if k.endswith(("_size", "_dim", "_rank", "_heads", "_tok"))]


def test_the_cell_is_found_with_its_readers():
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic_file"]["clients"] == 16
    assert cell["traffic_file"]["prefill_buckets"] == [64, 128, 256]
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name in ("moe_share", "moe_layer_roofline",
                 "expert_load_max_over_mean"):
        assert per_layer[name]["layer"] == "expert layer"
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert per_layer[name]["workloads"] == [CELL]
        assert manifest.layer_metric_reader(name) is not None
    assert {"engine_step_p50_ms.tput", "decode_step_roofline.tput",
            "decode_compute_share.tput", "slot_occupancy",
            "hbm_planned_share.serve_tput"} <= set(per_layer)
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    # the longest sequence of the mix fits a slot's context
    mix = cell["traffic_file"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= cell["config_file"]["serve"]["kv_context_per_slot"]


def test_the_byte_counts_follow_the_programs_shapes(config):
    import jax

    model = config["model"]
    cfg = olmoe_family.make_config(model)
    from paddle_tpu.models import olmoe

    shapes = jax.eval_shape(lambda k: olmoe.init(k, cfg)[0],
                            jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert olmoe_shapes.param_count(model) == n == 3562604544
    assert olmoe_shapes.expert_bytes(model) == 12582912
    assert olmoe_shapes.expected_experts_hit(model, 16) \
        == pytest.approx(56.44, abs=0.01)
    assert olmoe_family.kv_bytes_per_token(model) == 65536
    # a 16-row step with 7000 tokens resident: experts 5.68 of 6.62 GB
    least = olmoe_family.decode_step_min_bytes(model, 7000.0)
    experts = 8 * olmoe_shapes.expected_experts_hit(model, 16) * 12582912
    assert least == pytest.approx(6.62e9, rel=0.01)
    assert experts / least == pytest.approx(0.86, abs=0.01)
    assert olmoe_shapes.moe_layer_min_bytes(model, 451.0) \
        == pytest.approx(451 * 12582912 + 8 * 2048 * 64 * 2)


# -- the readers -------------------------------------------------------------


def _records(model, steps):
    decode = "jit__decode_fn"
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"modules": {decode: {"count": 100, "median_s": 0.02},
                              "jit__prefill_fn": {"count": 3,
                                                  "median_s": 0.015}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 2.0, "by_scope": {
                "mlp": 1.2, "attention": 0.3, "kv_gather": 0.3,
                "qkv": 0.1, "layers.other": 0.1}},
            "jit__prefill_fn": {"total_s": 0.045,
                                "by_scope": {"mlp": 0.03}}}},
        "program": {"steps": steps}}


def test_the_three_readers_on_records_made_by_hand(config):
    model = config["model"]
    steps = [{"kind": "decode", "slots": 16, "experts_hit": 440 + 2 * i,
              "expert_load_max": 6 + i % 3} for i in range(11)]
    steps.append({"kind": "prefill", "slots": 1})
    rec = _records(model, steps)
    read = manifest.layer_metric_reader
    assert read("moe_share")(rec) == pytest.approx(0.6)
    # 450 experts x 12.58 MB + routers = 5.66 GB: 6.92 ms at 819 GB/s,
    # against 1.2 s / 100 steps = 12 ms under `mlp`
    assert read("moe_layer_roofline")(rec) == pytest.approx(
        100 * (450 * 12582912 + 2097152) / 819e9 / 0.012)
    assert 50 < read("moe_layer_roofline")(rec) < 100
    assert read("expert_load_max_over_mean")(rec) == pytest.approx(
        np.mean([6 + i % 3 for i in range(11)]) / 2.0)


def test_the_readers_find_nothing_in_a_program_that_counts_no_experts(
        config):
    """The parent's program, or GPT-2's: step records without the
    counters, no `mlp` seconds that are an expert layer's: the metric is
    left out, nothing raises."""
    read = manifest.layer_metric_reader
    plain = [{"kind": "decode", "slots": 16} for _ in range(5)]
    rec = _records(config["model"], plain)
    assert read("moe_layer_roofline")(rec) is None
    assert read("expert_load_max_over_mean")(rec) is None
    for broken in ({"kind": "serve"}, {"kind": "train"},
                   dict(rec, scopes=None), dict(rec, trace=None),
                   dict(rec, program=None), dict(rec, peaks=None)):
        assert read("moe_layer_roofline")(broken) is None, broken.keys()
        read("moe_share")(broken), read("expert_load_max_over_mean")(broken)
    assert read("moe_share")(dict(rec, trace=None)) is None   # untraced
    assert read("expert_load_max_over_mean")(dict(rec, program=None)) is None


# -- a tiny rehearsal through the serve kind ----------------------------------

TINY_OLMOE = {
    "family": "olmoe",
    "model": {"vocab_size": 512, "hidden": 64, "layers": 2, "heads": 4,
              "expert_dim": 32, "n_experts": 8, "top_k": 2, "max_len": 128,
              "rope_theta": 10000.0, "rms_eps": 1e-5, "dtype": "bfloat16"},
    "serve": {"precision": "bf16", "block_size": 16, "decode_slots": [4],
              "kv_context_per_slot": 128, "eos_id": None, "max_queue": 64},
    "logit_gap_tol": 0.5}
TINY_MIX = {"kind": "serve", "loop": "closed", "clients": 4,
            "table_size": 24,
            "prompt_len": {"dist": "uniform", "lo": 4, "hi": 30},
            "output_len": {"dist": "uniform", "lo": 16, "hi": 40},
            "prefill_buckets": [16, 32], "lead_s": 0.5}


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


def test_tiny_gen_closed_rehearsal(tmp_path, jax_cache_config):
    from benchmarks.kinds import serve

    cell = {"name": "tiny.gen_closed", "chips": 1,
            "config_file": TINY_OLMOE, "traffic_file": TINY_MIX}
    args = types.SimpleNamespace(seed=2 ** 31 + 11, seconds=2.0, trace=0,
                                 rate=None, t_start=time.monotonic())
    res = serve.run(cell, args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["checks"]["compiles_in_window"] == 0
    assert res["checks"]["ref_tokens"] == 64
    mem = res["checks"]["memory"]
    base = mem["resident_at_start"]
    # the served set and the pools while the engine lives; when the
    # reference runs, only what lies outside the layers is resident in
    # float32 (embedding, final norm, head): the layers come one at a time
    assert mem["resident_bytes"] - base \
        <= mem["weight_bytes"] + mem["kv_pool_bytes"] + mem["weight_bytes"] // 4
    assert mem["resident_dropped"] - base <= mem["weight_bytes"] // 4
    top_f32 = 4 * (2 * 512 * 64 + 64)
    # (other tests' arrays may live in this process: bounds, not equality)
    assert top_f32 <= mem["resident_at_reference"] \
        <= mem["resident_dropped"] + top_f32 + 64      # + a key
    assert mem["kv_bytes_per_token"] == 2 * 2 * 64 * 2


def test_the_served_set_is_the_float32_one_rounded_once():
    import jax.numpy as jnp

    cfg = olmoe_family.make_config(TINY_OLMOE["model"])
    served, axes = olmoe_family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = olmoe_family.init(cfg, SEED)
    assert axes["blk.w_gate"] == ("layer", "expert", "embed", "mlp")
    for k, v in f32.top.items():
        assert v.dtype == jnp.float32 and served[k].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(v.astype(jnp.bfloat16)),
                              np.asarray(served[k])), k
    for i in range(cfg.layers):
        for k, v in f32.layer(i).items():
            # to the last bit but one: XLA may fold an init scale another
            # way in the program that makes every layer
            got = np.asarray(served[k][i].astype(jnp.float32))
            want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
            assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
            assert (got != want).mean() < 1e-3, k


# -- what the tolerance tells apart, at the published widths -----------------

LAYERS, T = 2, 160


@pytest.fixture(scope="module")
def published(config):
    """2 of the 8 layers at the published widths, one sequence of 160 seeded
    tokens: the PROGRAM's pick at every position (its full forward pass in
    bf16 from the served set; a decode step makes the same pick from the
    same prefix) is judged as the serve kind judges a streamed token: how
    far it lies, in the reference's float32 logits, below the reference's
    own argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import olmoe

    model = dict(config["model"], layers=LAYERS)
    cfg = olmoe_family.make_config(model)
    served, _ = olmoe_family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = olmoe_family.init(cfg, SEED)
    ids = jnp.asarray(traffic.prompt_ids(SEED, 0, T, model["vocab_size"]),
                      jnp.int32)
    picks = np.asarray(jax.jit(lambda p, i: olmoe.apply(p, cfg, i))(
        served, ids[None])[0].argmax(-1))
    return config, model, f32, ids, picks


def _gap(published, model=None, weights=None):
    import jax

    config, right, f32, ids, picks = published
    model = model or right
    weights = weights or (lambda v: v)
    top = {k: weights(v) for k, v in f32.top.items()}
    step = jax.jit(lambda lp, x: olmoe_ref.block(lp, x, model))
    with jax.default_matmul_precision("highest"):
        x = top["wte.w"][ids]
        for i in range(model["layers"]):
            x = step({k: weights(v) for k, v in f32.layer(i).items()}, x)
        rows = np.asarray(olmoe_ref.head_rows(top, model, x, 0, T))
    return float((rows.max(-1) - rows[np.arange(T), picks]).max())


def test_the_bf16_program_is_within_the_tolerance(published):
    assert _gap(published) <= published[0]["logit_gap_tol"] / 2


@pytest.mark.parametrize("fault, switch", [
    ("dropped_last_layer", {"layers": LAYERS - 1}),
    ("renormalised_probabilities", {"norm_topk_prob": True}),
    ("likeliest_expert_dropped", {"drop_expert_rank": 0}),
    ("queries_rotated_one_position_late", {"rope_q_offset": 1})])
def test_the_tolerance_fails_a_fault(published, fault, switch):
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], **switch)) > 2 * tol, fault


def test_float8_weights_are_not_correct(published):
    """The nearest precision below the stated one: the reference with
    its matrices rounded to float8 (e4m3) is over the tolerance, the same
    matrices rounded to bf16, which is what the program serves, under it."""
    import jax.numpy as jnp

    def rounded(dtype):
        return lambda v: v.astype(dtype).astype(jnp.float32) \
            if v.ndim >= 2 else v

    tol = published[0]["logit_gap_tol"]
    assert _gap(published, weights=rounded(jnp.float8_e4m3fn)) > tol
    assert _gap(published, weights=rounded(jnp.bfloat16)) <= tol / 2


def test_the_least_of_the_eight_experts_is_not_told_apart(published):
    """Leaving out a token's 8th expert (p about 0.02) moves a logit by no
    more than the bf16 program's own routing does when the 8th and 9th
    probabilities lie within a rounding of each other: the tolerance
    passes both, and the configuration file says so."""
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], drop_expert_rank=7)) <= tol
