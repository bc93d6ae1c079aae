"""The cell `jamba2_3b.chat_closed` off the chip: its configuration file
against the catalog row's keys, its byte counts against the program's
shapes, its new readers on records made by hand (and on another family's
records or the parent's: nothing, and no error), the traffic file's
parameters as ISSUE 49 names them, a tiny rehearsal through the serve kind,
the controls' script at a tiny size, and what `logit_gap_tol` tells apart
at the published widths (layers `ME*EME` of a period cut to three layers
and an eighth of the vocabulary, for the CPU)."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks.families import jamba as family
from benchmarks.harness import jamba_shapes as shapes, manifest, traffic
from benchmarks.reference import jamba_ref as ref_mod

CELL = "jamba2_3b.chat_closed"
SEED = 3000000049


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "jamba2_3b.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the catalog row's `config` stands at the top level
    under its own name; `model` repeats the sizes under the program's
    names, and only the key under `reduced` differs from the source."""
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"]) \
        == {"max_position_embeddings"}
    assert config["max_position_embeddings"] == 1024
    assert config["num_hidden_layers"] == 28 == config["model"]["n_layers"]
    for ours, theirs in config["source_keys"].items():
        assert config["model"][ours] == config[theirs], ours
    assert set(config["assumed"]) >= {
        "layer_types", "inner_norms", "init", "attention_scores",
        "positions", "state_dtype"}
    assert "ONE chip holds the whole model" in config["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "jamba2_3b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/jamba2_3b.json"
    assert config["logit_gap_tol_reason"] != "TO BE SET FROM CHIP READINGS"
    # the layer rule gives the published 13:1
    cfg = family.make_config(config["model"])
    assert cfg.pattern == ("ME" * 7 + "*E" + "ME" * 6) * 2
    assert ref_mod.pattern_of(config["model"]) == cfg.pattern


def test_the_cell_is_found_with_its_readers():
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    # ISSUE 49's traffic, letter for letter
    assert cell["chips"] == 1 and mix["kind"] == "serve"
    assert mix["loop"] == "closed" and mix["clients"] == 128
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 32, "hi": 512}
    assert mix["output_len"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert mix["prefill_buckets"] == [64, 128, 256, 512]
    assert mix["table_size"] == 512 and mix["lead_s"] == 20
    assert bench["run_seconds"] == 40
    rows = traffic.schedule(mix, SEED, 60.0)
    assert len(rows) == 512
    assert np.mean([r["prompt_len"] for r in rows]) == pytest.approx(
        173, abs=2)
    assert np.mean([r["max_new"] for r in rows]) == pytest.approx(320, abs=1)
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name, layer in (("selective_ssm_share", "recurrent layers"),
                        ("selective_update_roofline", "recurrent layers"),
                        ("selective_scan_roofline", "recurrent layers"),
                        ("state_rows_used_share.jamba", "decode engine")):
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
    assert not {"ssm_share", "ssm_update_roofline", "ssm_scan_roofline",
                "linear_state_roofline", "dense_mlp_roofline",
                "moe_share"} & set(per_layer)
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    serve = cell["config_file"]["serve"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= serve["kv_context_per_slot"] == cell["config_file"]["model"][
            "max_len"] == 1024
    assert mix["prompt_len"]["hi"] <= max(mix["prefill_buckets"])
    assert serve["decode_slots"] == [128] == [mix["clients"]]
    assert serve["block_size"] == 16 and serve["precision"] == "bf16"
    assert serve["state"] == {"ssm": "float32", "conv_tail": "bfloat16",
                              "rows": 129}
    # where the byte counts' slot count comes from (families/jamba.py)
    import inspect

    assert inspect.signature(shapes.decode_step_min_bytes).parameters[
        "slots"].default == max(serve["decode_slots"])


def test_the_byte_counts_follow_the_programs_shapes(config):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import jamba

    model = config["model"]
    cfg = family.make_config(model)
    made = jax.eval_shape(lambda k: jamba.init(k, cfg)[0],
                          jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in made.values())
    assert shapes.param_count(model) == n
    assert 2 * n == pytest.approx(6.06e9, rel=2e-3)
    # the issue's arithmetic, layer by layer
    assert shapes.mamba_layers(model) == 26
    assert shapes.attention_layers(model) == 2
    assert shapes.mamba_params(model) + shapes.mlp_params(model) \
        == pytest.approx(104.2e6, rel=1e-3)
    assert shapes.attention_params(model) + shapes.mlp_params(model) \
        == pytest.approx(76.7e6, rel=1e-3)
    assert shapes.top_params(model) == pytest.approx(167.8e6, rel=1e-3)
    # what a sequence holds, as the engine's own geometry says
    sm = cfg.serve_model()
    assert family.kv_bytes_per_token(model) == 1024 \
        == sm.kv_layers * sum(sm.stored) * 2
    pools = sm.state_pools(129, jnp.bfloat16)
    per_row = sum(int(np.prod(s[2:])) * jnp.dtype(dt).itemsize
                  for s, dt in pools)
    assert shapes.state_row_bytes(model) == per_row == 3 * 5120 * 2 \
        + 16 * 5120 * 4
    assert [s[:2] for s, _ in pools] == [(26, 129), (26, 129)]
    assert 26 * per_row == pytest.approx(9.32e6, rel=1e-3)  # a sequence
    # a 128-row step with 64000 tokens resident: 8.51 GB; the weights
    # 6.06, the state both ways 2.39 (tails 0.20), the cache 0.07
    least = family.decode_step_min_bytes(model, 64000.0)
    assert least == pytest.approx(8.51e9, rel=2e-3)
    assert least == 2 * n + 26 * 128 * 2 * per_row + 64000 * 1024
    mixers = shapes.ssm_step_min_bytes(model, 128)
    assert mixers == 26 * (2 * (shapes.mamba_params(model) - 2560)
                           + 128 * 2 * per_row)
    assert mixers / least == pytest.approx(0.53, abs=0.01)
    # a prompt's scan at the mix's mean length: counted against the bf16
    # peak the recurrence is little beside the projections, and the bytes
    # bind
    flops, moved = (shapes.scan_min_flops(model, 173),
                    shapes.scan_min_bytes(model, 173))
    assert flops == pytest.approx(173 * (
        2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
        + 6 * 16 * 5120 + 8 * 5120))
    assert moved / 819e9 > flops / 197e12


# -- the readers -------------------------------------------------------------

NEW = ("selective_ssm_share", "selective_update_roofline",
       "selective_scan_roofline", "state_rows_used_share.jamba")


def _records(model, steps, live=64000.0):
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": live,
                  "modules": {decode: {"count": 100, "median_s": 0.013},
                              prefill: {"count": 40, "median_s": 0.01}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 1.3, "by_scope": {
                "mlp": 0.45, "ssm": 0.7, "attention": 0.02, "qkv": 0.01,
                "head": 0.06, "layers.other": 0.06}},
            prefill: {"total_s": 0.4, "by_scope": {
                "mlp": 0.15, "ssm": 0.2, "attention": 0.01}}}},
        "program": {"steps": steps}}


def _steps():
    steps = [{"kind": "decode", "slots": 128, "state_rows": 128,
              "state_rows_used": 124 + i % 5} for i in range(10)]
    steps += [{"kind": "prefill", "slots": 1, "live_tokens": n}
              for n in (40, 173, 306)]
    return steps


def test_the_new_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    assert read("selective_ssm_share")(rec) == pytest.approx(0.7 / 1.3)
    # 26 x (82 MB of weights + 128 rows x 2 x 358 KB) = 4.53 GB: 5.53 ms at
    # 819 GB/s, against 0.7 s / 100 steps = 7 ms under `ssm`
    assert read("selective_update_roofline")(rec) == pytest.approx(
        100 * shapes.ssm_step_min_bytes(model, 128) / 819e9 / 0.007)
    assert 75 < read("selective_update_roofline")(rec) < 83
    # a mean prompt of 173 tokens through 26 layers: the bytes bind
    want = 26 * shapes.scan_min_bytes(model, 173) / 819e9
    assert read("selective_scan_roofline")(rec) == pytest.approx(
        100 * want / (0.2 / 40))
    assert read("state_rows_used_share.jamba")(rec) == pytest.approx(
        126 / 128)
    # the shared readers serve this model through the family's byte count
    assert read("decode_step_roofline.tput")(dict(rec, trace=dict(
        rec["trace"], decode_min_bytes=family.decode_step_min_bytes(
            model, 64000.0)))) == pytest.approx(
        100 * 8.51e9 / 819e9 / 0.013, rel=2e-3)


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """Another family's records (Nemotron's, OLMoE's), the parent's program
    (no `ssm` scope on any op, no trace): the metric is left out, nothing
    raises; and the older families' readers give nothing for this one."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "nemotron3_nano.json")) as f:
        nemotron = json.load(f)["model"]
    plain = [{"kind": "decode", "slots": 16} for _ in range(5)]
    for name in NEW:
        assert read(name)(_records(olmoe, plain)) is None, name
        if not name.startswith("state_rows"):   # a counter: needs no trace
            assert read(name)(_records(nemotron, _steps())) is None, name
            assert read(name)(dict(rec, trace=None)) is None, name
        for broken in ({"kind": "serve"}, {"kind": "train"},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None)):
            read(name)(broken)                      # and nothing raises
    bare = _records(config["model"], _steps())
    for prog in bare["scopes"]["programs"].values():
        del prog["by_scope"]["ssm"]
    for name in NEW[:3]:
        assert read(name)(bare) is None, name
    for theirs in ("ssm_share", "ssm_update_roofline", "ssm_scan_roofline",
                   "linear_state_roofline", "linear_attention_share",
                   "dense_mlp_roofline"):
        assert read(theirs)(rec) is None, theirs


def test_the_family_registers_its_scope_with_the_reduction(config):
    from benchmarks.harness import program_trace

    cfg = family.make_config(dict(config["model"], scalar_decay=True))
    assert not hasattr(cfg, "scalar_decay")     # the reference's switch
    family.register_scopes()                        # idempotent
    assert program_trace.SCOPES.count("ssm") == 1
    assert program_trace.COMPUTE.count("ssm") == 1
    op = "jit(_decode_fn)/jit(main)/layers/ssm/scan/pallas_call"
    assert program_trace.scope_of(op) == "ssm"
    assert family.is_jamba({"model": config["model"]})
    assert not family.is_jamba({"model": {"ssm_state": 128}})


# -- a tiny rehearsal through the serve kind ----------------------------------

TINY = {
    "family": "jamba",
    "model": {"vocab_size": 512, "hidden": 64, "n_layers": 4,
              "attn_period": 2, "attn_offset": 1, "mlp_dim": 128,
              "expand": 2, "ssm_state": 16, "dt_rank": 8, "conv_kernel": 4,
              "heads": 5, "kv_heads": 1, "head_dim": 16,
              "max_len": 128, "rms_eps": 1e-6, "dtype": "bfloat16"},
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


def test_tiny_chat_closed_rehearsal(tmp_path, jax_cache_config):
    from benchmarks.kinds import serve
    from tests.benchmarks import jamba_control

    cell = {"name": "tiny.chat_closed", "chips": 1,
            "config_file": TINY, "traffic_file": TINY_MIX}
    args = types.SimpleNamespace(seed=2 ** 31 + 49, seconds=2.0, trace=0,
                                 rate=None, t_start=time.monotonic())
    res = serve.run(cell, args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["checks"]["compiles_in_window"] == 0
    assert res["checks"]["ref_tokens"] == 64
    mem = res["checks"]["memory"]
    # K/V for the two attention layers: ONE K/V head of 16, bf16
    assert mem["kv_bytes_per_token"] == 2 * 2 * 16 * 2
    assert mem["kv_pool_bytes"] == (4 * 8 + 1) * 16 * mem["kv_bytes_per_token"]
    # the controls as the chip runs them, on this run's own files: the
    # served streams are within rounding, a fault is not
    with open(os.path.join(str(tmp_path), "requests.jsonl")) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    sample = jamba_control.sample_of(requests, args.seed)
    assert [r["idx"] for r in sample] == res["checks"]["sampled"]
    model = TINY["model"]
    cfg = family.make_config(model)
    got = jamba_control.readings(
        lambda: family.init(cfg, args.seed)[0], model,
        [traffic.prompt_ids(args.seed, r["idx"], r["prompt_len"], 512)
         for r in sample], [r["tokens"][:16] for r in sample],
        ["skip_D", "bf16"])
    assert got["program"] == pytest.approx(
        res["checks"]["ref_max_logit_gap"], abs=1e-5)
    assert got["skip_D"] > 4 * max(got["program"], got["bf16"], 0.01)
    assert set(s for f in jamba_control.SWITCHES.values() for s in f) \
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
    seen = {}
    for i, kind in enumerate(cfg.pattern):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        layer = f32.layer(i)
        assert ("blk.in_proj" in layer) == (kind == "M")
        assert ("blk.w_gate" in layer) == (kind == "E")
        assert ("blk.wq" in layer) == (kind == "*")
        prefix = ref_mod.PREFIX[kind]
        for k, v in layer.items():
            got = np.asarray(served[prefix + k[4:]][nth].astype(jnp.float32))
            want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
            assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
            assert (got != want).mean() < 1e-3, k


# -- what the tolerance tells apart, at the published widths -----------------

T = 32
CUT = {"n_layers": 3, "attn_period": 3, "attn_offset": 1,
       "vocab_size": 8192}


@pytest.fixture(scope="module")
def published(config):
    """The published widths, three layers `ME*EME` (a period cut to three)
    and an eighth of the vocabulary for the CPU, one sequence of 32 seeded
    tokens: the PROGRAM's pick at every position (its full forward pass in
    bf16 from the served set; prefill and decode steps make the same pick
    from the same prefix, tests/test_jamba.py) is judged as the serve kind
    judges a streamed token: how far it lies, in the reference's float32
    logits, below the reference's own argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import jamba

    model = dict(config["model"], **CUT)
    cfg = family.make_config(model)
    served, _ = family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = family.init(cfg, SEED)
    ids = jnp.asarray(traffic.prompt_ids(SEED, 0, T, model["vocab_size"]),
                      jnp.int32)
    picks = np.asarray(jax.jit(lambda p, i: jamba.apply(p, cfg, i))(
        served, ids[None])[0].argmax(-1))
    return config, model, f32, ids, picks


def _gap(published, model=None, weights=None):
    """`weights(name, value)`: a control on the reference's parameters."""
    import jax

    config, right, f32, ids, picks = published
    model = model or right
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, v) for k, v in f32.top.items()}
    pattern = ref_mod.pattern_of(model)
    steps = {kind: jax.jit(lambda lp, x, kind=kind: ref_mod.block(
        lp, x, model, kind, model.get("prompt_len")))
        for kind in set(pattern)}
    with jax.default_matmul_precision("highest"):
        x = top["wte.w"][ids]
        for i, kind in enumerate(pattern):
            x = steps[kind]({k: weights(k, v)
                             for k, v in f32.layer(i).items()}, x)
        rows = np.asarray(ref_mod.head_rows(top, model, x, 0, T))
    return ref_mod.verdict(rows.max(-1) - rows[np.arange(T), picks])


def test_the_bf16_program_is_within_the_tolerance(published):
    assert _gap(published) <= published[0]["logit_gap_tol"] / 2


@pytest.mark.parametrize("fault, switch", [
    ("one_decay_a_channel", {"scalar_decay": True}),
    ("dt_norm_left_out", {"dt_norm": False}),
    ("B_norm_left_out", {"b_norm": False}),
    ("C_norm_left_out", {"c_norm": False}),
    ("conv_bias_dropped", {"conv_bias": False}),
    ("dt_bias_left_out", {"dt_bias": False}),
    ("D_dropped", {"skip_D": True}),
    ("rotary_positions_applied", {"rope": True}),
    ("positions_added", {"learned_pos": True}),
    ("padded_tail_advances_the_state", {"pad_tail": 16, "prompt_len": 16}),
    ("tail_from_the_buckets_end", {"pad_conv": 3, "prompt_len": 16})])
def test_the_tolerance_fails_a_fault(published, fault, switch):
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], **switch)) > tol, fault


def test_float8_weights_are_not_correct(published):
    """The nearest precision below the stated one: the reference with
    its matrices rounded to float8 (e4m3) is over the tolerance, the same
    matrices rounded to bf16, which is what the program serves, under it."""
    import jax.numpy as jnp

    from tests.benchmarks import jamba_control

    tol = published[0]["logit_gap_tol"]
    assert _gap(published,
                weights=jamba_control.rounded(jnp.float8_e4m3fn)) > tol
    assert _gap(published,
                weights=jamba_control.rounded(jnp.bfloat16)) <= tol / 2
