"""The cell `nemotron3_nano.reason_closed` off the chip: its configuration
file against its source's keys, its byte counts against the program's
shapes, its new readers on records made by hand (and on a program without
recurrent layers or counters: nothing, and no error), a tiny rehearsal
through the serve kind, and what `logit_gap_tol` tells apart at the
published widths (blocks `MEM*E` of the pattern, 16 of the 128 experts and
an eighth of the vocabulary, for the CPU)."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.harness import manifest, nemotron_h_shapes as shapes, traffic
from benchmarks.reference import nemotron_h_ref as ref_mod

CELL = "nemotron3_nano.reason_closed"
SEED = 3000000019


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "nemotron3_nano.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the source's config.json stands at the top level under
    its own name; `model` repeats the sizes under the program's names, and
    only the keys under `reduced` differ from the source."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"])
    assert config["num_hidden_layers"] == 9 == len(config["model"]["pattern"])
    # one whole period: the published pattern's first 9 characters
    assert config["hybrid_override_pattern"] == "MEMEM*EME" \
        == published["hybrid_override_pattern"][:9]
    assert config["max_position_embeddings"] == 2560
    for ours, theirs in config["source_keys"].items():
        assert config["model"][ours] == config[theirs], ours
    assert set(config["assumed"]) >= {"init", "router_bias", "positions",
                                      "expand", "state_dtype"}
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "nemotron3_nano")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # no width is cut: experts, experts a token and the vocabulary are whole
    assert not [k for k in config["reduced"]
                if k.endswith(("_size", "_dim", "_rank", "_heads", "_tok",
                               "_experts"))]
    assert config["logit_gap_tol_reason"] != "TO BE SET FROM CHIP READINGS"


def test_the_cell_is_found_with_its_readers():
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    assert cell["chips"] == 1 and mix["clients"] == 64
    assert mix["prefill_buckets"] == [256, 512, 768, 1024]
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 256,
                                 "hi": 1024}
    assert mix["output_len"] == {"dist": "uniform", "lo": 512, "hi": 1536}
    assert mix["loop"] == "closed" and mix["table_size"] >= 256
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name, layer in (("ssm_share", "recurrent layers"),
                        ("ssm_update_roofline", "recurrent layers"),
                        ("ssm_scan_roofline", "recurrent layers"),
                        ("relu2_expert_roofline", "expert layer"),
                        ("gqa_attention_roofline", "decode kernels"),
                        ("moe_share.nemotron3_nano", "expert layer"),
                        ("expert_load_max_over_mean.nemotron3_nano",
                         "expert layer"),
                        ("state_rows_used_share", "decode engine")):
        assert per_layer[name]["layer"] == layer
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert per_layer[name]["workloads"] == [CELL]
        assert manifest.layer_metric_reader(name) is not None
    assert not {"moe_share", "expert_load_max_over_mean",
                "moe_layer_roofline", "expert_layer_roofline",
                "latent_attention_share"} & set(per_layer)
    assert {"engine_step_p50_ms.tput", "decode_step_roofline.tput",
            "decode_compute_share.tput", "slot_occupancy",
            "engine_prefill_share.tput", "engine_host_share.tput",
            "prefill_gap_share.tput", "stream_gap_p95_ms",
            "kv_block_used_share.tput", "device_idle_share.serve_tput",
            "hbm_planned_share.serve_tput"} <= set(per_layer)
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    # the longest sequence of the mix fits a slot's context, every prompt a
    # bucket, and every client a slot
    serve = cell["config_file"]["serve"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= serve["kv_context_per_slot"] == cell["config_file"]["model"][
            "max_len"] == 2560
    assert mix["prompt_len"]["hi"] <= max(mix["prefill_buckets"])
    assert serve["decode_slots"] == [64] == [mix["clients"]]
    assert serve["block_size"] == 16
    # where the byte counts' slot count comes from (families/nemotron_h.py)
    import inspect

    assert inspect.signature(shapes.decode_step_min_bytes).parameters[
        "slots"].default == max(serve["decode_slots"])
    assert serve["state"]["rows"] == max(serve["decode_slots"]) + 1


def test_the_byte_counts_follow_the_programs_shapes(config):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import nemotron_h

    model = config["model"]
    cfg = family.make_config(model)
    made = jax.eval_shape(lambda k: nemotron_h.init(k, cfg)[0],
                          jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in made.values())
    # the program lays the routed experts out at 1920 lanes; the counts are
    # at the published 1856
    padding = 4 * 128 * 2 * 2688 * (cfg.expert_pad - cfg.expert_dim)
    assert cfg.expert_pad == 1920 and shapes.param_count(model) == n - padding
    assert 2 * shapes.param_count(model) == pytest.approx(12.146e9, rel=1e-3)
    # the issue's arithmetic, block by block, and the whole model's
    assert shapes.mamba_params(model) == pytest.approx(38.74e6, rel=1e-3)
    assert shapes.moe_params(model) == pytest.approx(1297.47e6, rel=1e-4)
    assert shapes.attention_params(model) == pytest.approx(23.40e6, rel=1e-3)
    assert shapes.top_params(model) == pytest.approx(704.65e6, rel=1e-4)
    full = dict(model, pattern=dataclasses.asdict(
        nemotron_h.NemotronHConfig())["pattern"])
    assert len(full["pattern"]) == 52
    assert [full["pattern"].count(k) for k in "ME*"] == [23, 23, 6]
    assert shapes.param_count(full) == pytest.approx(31.58e9, rel=1e-3)
    assert shapes.active_params(full) == pytest.approx(3.23e9, rel=2e-3)
    assert shapes.expert_bytes(model) == 2 * 2688 * 1856 * 2 == 19955712
    assert shapes.expected_experts_hit(model, 64) == pytest.approx(122.1,
                                                                   abs=0.1)
    # what a sequence holds, as the engine's own geometry says
    sm = cfg.serve_model()
    assert family.kv_bytes_per_token(model) == 1024 \
        == sm.kv_layers * sum(sm.stored) * 2
    pools = sm.state_pools(65, jnp.bfloat16)
    per_row = sum(int(np.prod(s[2:])) * jnp.dtype(dt).itemsize
                  for s, dt in pools)
    assert shapes.state_row_bytes(model) == per_row == 3 * 6144 * 2 \
        + 64 * 64 * 128 * 4
    assert [s[:2] for s, _ in pools] == [(4, 65), (4, 65)]
    # a 64-row step with 96000 tokens resident: 12.2 GB, the routed experts
    # 9.7 of them, the state 1.1, the cache 0.1
    least = family.decode_step_min_bytes(model, 96000.0)
    assert least == pytest.approx(12.2e9, rel=0.01)
    experts = 4 * shapes.expected_experts_hit(model, 64) * 19955712
    assert experts == pytest.approx(9.74e9, rel=0.01)
    assert 4 * 64 * 2 * per_row == pytest.approx(1.09e9, rel=0.01)
    assert shapes.ssm_step_min_bytes(model, 64) == pytest.approx(
        4 * 38744896 * 2 + 4 * 64 * 2 * per_row)
    assert shapes.mlp_min_bytes(model, 488.0) == pytest.approx(
        (4 * (2688 * 128 + 128 + 2 * 2688 * 3712) + 488 * 2 * 2688 * 1856)
        * 2)
    assert shapes.attention_min_bytes(model, 96000.0) == 96000 * 1024
    # a prompt's scan: compute binds it (the projections), not the bytes
    flops, moved = (shapes.scan_min_flops(model, 554),
                    shapes.scan_min_bytes(model, 554))
    assert flops / 197e12 > moved / 819e9
    assert flops == pytest.approx(554 * (2 * 2688 * 10304 + 2 * 4096 * 2688
                                         + 4 * 4096 * 128 + 8 * 6144))


# -- the readers -------------------------------------------------------------

NEW = ("ssm_share", "ssm_update_roofline", "ssm_scan_roofline",
       "relu2_expert_roofline", "gqa_attention_roofline",
       "state_rows_used_share")


def _records(model, steps, live=96000.0):
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": live,
                  "modules": {decode: {"count": 100, "median_s": 0.02},
                              prefill: {"count": 5, "median_s": 0.1}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 2.0, "by_scope": {
                "mlp": 1.4, "ssm": 0.3, "attention": 0.02, "qkv": 0.03,
                "head": 0.15, "layers.other": 0.1}},
            prefill: {"total_s": 0.5, "by_scope": {
                "mlp": 0.3, "ssm": 0.1, "attention": 0.05}}}},
        "program": {"steps": steps}}


def _steps():
    steps = [{"kind": "decode", "slots": 64, "experts_hit": 480 + 2 * i,
              "expert_load_max": 8 + i % 3, "state_rows": 64,
              "state_rows_used": 60 + i % 5} for i in range(10)]
    steps += [{"kind": "prefill", "slots": 1, "live_tokens": n}
              for n in (300, 500, 862)]
    return steps


def test_the_new_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    assert read("ssm_share")(rec) == pytest.approx(0.15)
    # 4 x (77.5 MB of weights + 64 rows x 2 x 2.13 MB) = 1.40 GB: 1.71 ms at
    # 819 GB/s, against 0.3 s / 100 steps = 3 ms under `ssm`
    assert read("ssm_update_roofline")(rec) == pytest.approx(
        100 * shapes.ssm_step_min_bytes(model, 64) / 819e9 / 0.003)
    assert 50 < read("ssm_update_roofline")(rec) < 65
    # a mean prompt of 554 tokens through 4 blocks: compute binds it
    want = 4 * shapes.scan_min_flops(model, 554) / 197e12
    assert read("ssm_scan_roofline")(rec) == pytest.approx(
        100 * want / (0.1 / 5))
    # 489 experts x 19.96 MB + 4 x (router + shared) = 9.93 GB: 12.1 ms,
    # against 1.4 s / 100 steps = 14 ms under `mlp`
    assert read("relu2_expert_roofline")(rec) == pytest.approx(
        100 * shapes.mlp_min_bytes(model, 489.0) / 819e9 / 0.014)
    assert 80 < read("relu2_expert_roofline")(rec) < 92
    # 96000 tokens x 1024 B = 98 MB: 0.12 ms, against 0.2 ms
    assert read("gqa_attention_roofline")(rec) == pytest.approx(
        100 * 96000 * 1024 / 819e9 / 0.0002)
    assert read("state_rows_used_share")(rec) == pytest.approx(62 / 64)
    # the readers the benchmark had serve this model too
    assert read("moe_share.nemotron3_nano")(rec) == pytest.approx(0.7)
    assert read("expert_load_max_over_mean.nemotron3_nano")(rec) \
        == pytest.approx(np.mean([8 + i % 3 for i in range(10)]) / 3.0)


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """The parent's program, or GPT-2's, OLMoE's or JoyAI's: no recurrent
    layers in the model group, no `ssm` scope on any op, step records
    without rows or counters, no trace: the metric is left out, nothing
    raises."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    plain = [{"kind": "decode", "slots": 16} for _ in range(5)]
    for name in NEW:
        assert read(name)(_records(olmoe, plain)) is None, name
        if name != "state_rows_used_share":     # a counter: needs no trace
            assert read(name)(dict(rec, trace=None)) is None, name
        for broken in ({"kind": "serve"}, {"kind": "train"},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None)):
            read(name)(broken)                      # and nothing raises
    # a program whose ops carry no `ssm` scope (the parent's reduction)
    bare = _records(config["model"], _steps())
    for prog in bare["scopes"]["programs"].values():
        del prog["by_scope"]["ssm"]
    for name in ("ssm_share", "ssm_update_roofline", "ssm_scan_roofline"):
        assert read(name)(bare) is None, name
    assert read("state_rows_used_share")(_records(config["model"],
                                                  plain)) is None
    assert read("relu2_expert_roofline")(_records(config["model"],
                                                  plain)) is None
    no_live = dict(rec, trace={k: v for k, v in rec["trace"].items()
                               if k != "live_tokens_mean"})
    assert read("gqa_attention_roofline")(no_live) is None


def test_the_family_registers_its_scope_with_the_reduction(config):
    """`ssm` is no scope of the harness's own list (a file this PR may not
    edit); building this family's model makes it one, and only then."""
    from benchmarks.harness import program_trace

    family.make_config(config["model"])
    family.register_scopes()                        # idempotent
    assert program_trace.SCOPES.count("ssm") == 1
    assert program_trace.COMPUTE.count("ssm") == 1
    op = "jit(_decode_fn)/jit(main)/layers/ssm/scan/pallas_call"
    assert program_trace.scope_of(op) == "ssm"
    assert program_trace.scope_of(
        "jit(_decode_fn)/jit(main)/layers/mlp/experts/x") == "mlp"


# -- a tiny rehearsal through the serve kind ----------------------------------

TINY = {
    "family": "nemotron_h",
    "model": {"vocab_size": 512, "hidden": 64, "pattern": "MEM*E",
              "ssm_heads": 8, "ssm_head_dim": 8, "ssm_groups": 2,
              "ssm_state": 16, "conv_kernel": 4, "chunk": 8,
              "expert_dim": 24, "shared_dim": 48, "n_experts": 8, "top_k": 2,
              "route_scale": 2.5, "heads": 4, "kv_heads": 2, "head_dim": 16,
              "max_len": 128, "rms_eps": 1e-5, "dtype": "bfloat16"},
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


def test_tiny_reason_closed_rehearsal(tmp_path, jax_cache_config):
    from benchmarks.kinds import serve

    cell = {"name": "tiny.reason_closed", "chips": 1,
            "config_file": TINY, "traffic_file": TINY_MIX}
    args = types.SimpleNamespace(seed=2 ** 31 + 19, seconds=2.0, trace=0,
                                 rate=None, t_start=time.monotonic())
    res = serve.run(cell, args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["checks"]["compiles_in_window"] == 0
    assert res["checks"]["ref_tokens"] == 64
    mem = res["checks"]["memory"]
    # K/V for the one attention block alone: 2 K/V heads of 16, bf16
    assert mem["kv_bytes_per_token"] == 2 * 32 * 2
    assert mem["kv_pool_bytes"] == (4 * 8 + 1) * 16 * mem["kv_bytes_per_token"]
    state = 2 * 5 * (3 * 96 * 2 + 8 * 8 * 16 * 4)   # blocks x rows x a row
    base = mem["resident_at_start"]
    assert mem["resident_bytes"] - base <= mem["weight_bytes"] \
        + mem["kv_pool_bytes"] + state + mem["weight_bytes"] // 4
    assert mem["resident_dropped"] - base <= mem["weight_bytes"] // 4


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
    seen = {}
    for i, kind in enumerate(cfg.pattern):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        layer = f32.layer(i)
        assert ("blk.in_proj" in layer) == (kind == "M")
        assert ("blk.router" in layer) == (kind == "E")
        assert ("blk.wq" in layer) == (kind == "*")
        prefix = ref_mod.PREFIX[kind]
        for k, v in layer.items():
            stack = served[prefix + k[4:]][nth]
            if k == "blk.w_up":         # the reference's are unpadded
                assert v.shape[-1] == cfg.expert_dim
                stack = stack[..., :cfg.expert_dim]
            if k == "blk.w_down":
                stack = stack[:, :cfg.expert_dim]
            got = np.asarray(stack.astype(jnp.float32))
            want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
            assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
            assert (got != want).mean() < 1e-3, k


# -- what the tolerance tells apart, at the published widths -----------------

T = 64
CUT = {"pattern": "MEM*E", "n_experts": 16, "vocab_size": 16384}


@pytest.fixture(scope="module")
def published(config):
    """The published widths, blocks `MEM*E` of the pattern, with the expert
    count (16 of 128; still top-6) and the vocabulary (an eighth) cut for
    the CPU, one sequence of 64 seeded tokens: the PROGRAM's pick at every
    position (its full forward pass in bf16 from the served set; prefill
    and decode steps make the same pick from the same prefix,
    tests/test_nemotron_h.py) is judged as the serve kind judges a streamed
    token: how far it lies, in the reference's float32 logits, below the
    reference's own argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import nemotron_h

    model = dict(config["model"], **CUT)
    cfg = family.make_config(model)
    served, _ = family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = family.init(cfg, SEED)
    ids = jnp.asarray(traffic.prompt_ids(SEED, 0, T, model["vocab_size"]),
                      jnp.int32)
    picks = np.asarray(jax.jit(lambda p, i: nemotron_h.apply(p, cfg, i))(
        served, ids[None])[0].argmax(-1))
    return config, model, f32, ids, picks


def _gap(published, model=None, weights=None):
    """`weights(name, value)`: a control on the reference's parameters."""
    import jax

    config, right, f32, ids, picks = published
    model = model or right
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, v) for k, v in f32.top.items()}
    steps = {kind: jax.jit(lambda lp, x, kind=kind: ref_mod.block(
        lp, x, model, kind, model.get("prompt_len")))
        for kind in set(model["pattern"])}
    with jax.default_matmul_precision("highest"):
        x = top["wte.w"][ids]
        for i, kind in enumerate(model["pattern"]):
            x = steps[kind]({k: weights(k, v)
                             for k, v in f32.layer(i).items()}, x)
        rows = np.asarray(ref_mod.head_rows(top, model, x, 0, T))
    return ref_mod.verdict(rows.max(-1) - rows[np.arange(T), picks])


def test_the_bf16_program_is_within_the_tolerance(published):
    assert _gap(published) <= published[0]["logit_gap_tol"] / 2


@pytest.mark.parametrize("fault, switch", [
    ("conv_bias_dropped", {"conv_bias": False}),
    ("D_dropped", {"skip_D": True}),
    ("one_norm_group_of_4096", {"norm_groups": 1}),
    ("dt_bias_left_out", {"dt_bias": False}),
    ("relu_for_relu2", {"act": "relu"}),
    ("silu_for_relu2", {"act": "silu"}),
    ("shared_expert_dropped", {"shared_expert": False}),
    ("scale_2.5_left_out", {"route_scale": 1.0}),
    ("rotary_positions_applied", {"rope": True}),
    ("stale_state_row", {"stale_state": 64}),
    ("padded_tail_advances_the_state", {"pad_tail": 32, "prompt_len": 32})])
def test_the_tolerance_fails_a_fault(published, fault, switch):
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], **switch)) > tol, fault


def test_a_bf16_ssm_state_is_inside_the_tolerance(published):
    """What the comparison does NOT tell apart, held so that nobody reads
    the tolerance as a guard of the state's precision: with Mamba-2's own
    draw of dt and A a head forgets within some tens of tokens, and a state
    rounded to bf16 after every token moves the logits no further than the
    program's own bf16 rounding does (`logit_gap_tol_reason` has the chip's
    readings). tests/test_nemotron_h.py pins the float32 state at a tiny
    size (`bf16-state`), and the served pool's dtype is the model's
    (`test_the_pools_are_the_models`)."""
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], state_dtype="bfloat16")) \
        <= tol / 2


def test_float8_weights_are_not_correct(published):
    """The nearest precision below the stated one: the reference with
    its matrices rounded to float8 (e4m3) is over the tolerance, the same
    matrices rounded to bf16, which is what the program serves, under it."""
    import jax.numpy as jnp

    def rounded(dtype):
        return lambda k, v: v.astype(dtype).astype(jnp.float32) \
            if v.ndim >= 2 and k != "blk.conv_w" else v

    tol = published[0]["logit_gap_tol"]
    assert _gap(published, weights=rounded(jnp.float8_e4m3fn)) > tol
    assert _gap(published, weights=rounded(jnp.bfloat16)) <= tol / 2
