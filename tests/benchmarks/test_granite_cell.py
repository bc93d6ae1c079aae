"""The cell `granite4_h_small.ctx4k_sessions` off the chip: its configuration
file against its source's keys (it differs in `reduced` and nowhere else),
the cell found with its readers and the traffic ISSUE 58 gives, its byte
counts against the parameters' own sizes, its readers on records made by
hand (and on the other families' records and the parent's program: nothing,
and no error), a tiny traced rehearsal through the `sessions` kind, and what
`logit_gap_tol` tells apart at the published widths (layers `M*` of the
pattern, 8 held of 16 experts and an eighth of the vocabulary, for the
CPU)."""

import copy
import dataclasses
import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import granite_hybrid as family
from benchmarks.harness import (granite_hybrid_shapes as shapes, manifest,
                                traffic)
from benchmarks.kinds import sessions
from benchmarks.reference import granite_hybrid_ref as ref_mod
from tests.benchmarks.test_nemotron_cell import jax_cache_config  # noqa: F401

CELL = "granite4_h_small.ctx4k_sessions"
SEED = 3000000031
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "granite4_h_small.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352}


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the source's config.json stands at the top level under
    its own name; `model` repeats the sizes under the program's names, and
    only the keys under `reduced` differ from the source."""
    if os.path.exists(CATALOG):     # the catalog's row is what was copied
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-small")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"])
    model = config["model"]
    assert config["num_hidden_layers"] == 10 == len(model["pattern"])
    # one whole period: the published pattern's first ten entries
    assert config["layer_types"] == PUBLISHED["layer_types"][:10]
    assert model["pattern"] == "".join(
        {"mamba": "M", "attention": "*"}[t] for t in config["layer_types"])
    assert config["num_local_experts"] == 36 \
        == model["held"][1] - model["held"][0]
    assert model["n_experts"] == PUBLISHED["num_local_experts"]
    assert config["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    for ours, theirs in config["source_keys"].items():
        key = theirs.split(" ")[0]
        if ours in ("pattern", "held", "n_experts", "head_dim"):
            continue            # held above
        assert model[ours] == config[key], ours
    assert model["head_dim"] * model["heads"] == model["hidden"]
    assert set(config["assumed"]) >= {"expert_width", "init", "positions",
                                      "state_dtype", "tail"}
    assert "2 chips share each layer x 4 pipeline stages" \
        in config["deployment"] and "9.51 GB" in config["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "granite4_h_small")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # no width is cut
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_heads", "_tok",
                               "intermediate_size", "hidden_size"))]
    assert "TO BE SET" not in config["logit_gap_tol_reason"]
    # the program's configuration takes the group as it stands
    cfg = family.make_config(dict(model, rope=True, act="none"))
    assert cfg.held == (0, 36) and cfg.count("M") == 9 \
        and cfg.count("E") == 10 and cfg.count("*") == 1


def test_the_cell_is_found_with_its_readers(config):
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    assert cell["chips"] == 1 and mix["kind"] == "sessions"
    assert mix["clients"] == mix["table_size"] == 48 \
        == max(config["serve"]["decode_slots"])
    assert config["serve"]["state"]["rows"] == 49
    assert mix["prefill_buckets"] == [2048, 3072, 4096]
    assert all(b % config["model"]["prompt_slice"] == 0
               for b in mix["prefill_buckets"])
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 2048,
                                 "hi": 4096}
    assert mix["output_len"] == {"dist": "fixed", "value": 4096}
    assert mix["context_per_slot"] == 8192 and mix["weights_seed"] == 20261005
    assert mix["lead_s"] >= 10 and mix["lead_s"] % 5 == 0
    assert "TO BE" not in mix["note"]
    served = sessions.with_context(cell["config_file"], mix)
    assert served["model"]["max_len"] == 8192 \
        == served["serve"]["kv_context_per_slot"]
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name, layer, moves in (
            ("ssm_share.granite4", "recurrent layers", "serve_tokens_per_s"),
            ("ssm_update_roofline.granite4", "recurrent layers",
             "serve_tokens_per_s"),
            ("moe_share.granite4", "expert layer", "serve_tokens_per_s"),
            ("held_swiglu_expert_roofline", "expert layer",
             "serve_tokens_per_s"),
            ("gqa_attention_roofline.granite4", "decode kernels",
             "serve_tokens_per_s"),
            ("held_pair_share.granite4", "expert layer",
             "serve_tokens_per_s"),
            ("held_expert_load_max_over_mean.granite4", "expert layer",
             "serve_tokens_per_s"),
            ("state_rows_used_share.granite4", "decode engine",
             "serve_tokens_per_s"),
            ("window_admissions.granite4", "entry", "serve_tokens_per_s"),
            ("sessions_ready_s.granite4", "boot", "setup_s"),
            ("stream_silence_share.granite4", "entry",
             "serve_tokens_per_s")):
        assert per_layer[name]["layer"] == layer
        assert per_layer[name]["moves"] == moves
        assert per_layer[name]["workloads"] == [CELL]
        assert manifest.layer_metric_reader(name) is not None
    assert {"engine_step_p50_ms.tput", "decode_step_roofline.tput",
            "decode_compute_share.tput", "slot_occupancy",
            "engine_host_share.tput", "stream_gap_p95_ms",
            "kv_block_used_share.tput", "device_idle_share.serve_tput",
            "hbm_planned_share.serve_tput", "setup_engine_warm_s.sessions",
            "setup_compile_s"} <= set(per_layer)
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) == 13 and len(bench["configs"]) == 10
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["bert_base.dp4"]


def test_the_byte_counts_follow_the_programs_shapes(config):
    """`granite_hybrid_shapes` against the parameters' own sizes (shapes
    alone: nothing of 9.5 GB is made), the pools against the model's."""
    import jax

    from paddle_tpu.models import granite_hybrid

    model = config["model"]
    cfg = family.make_config(model)
    params = jax.eval_shape(lambda k: granite_hybrid.init(k, cfg)[0],
                            jax.random.key(0))
    sizes = {k: int(np.prod(v.shape)) for k, v in params.items()}
    assert shapes.param_count(model) == sum(sizes.values()) == 4_757_211_776

    def of(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    assert shapes.mamba_params(model) * 9 == of("mamba.")
    assert shapes.attention_params(model) == of("attn.")
    assert shapes.moe_params(model) * 10 == of("moe.")
    assert shapes.top_params(model) == of("wte.") + of("ln_f.")
    assert shapes.expert_params(model) * 36 * 10 \
        == sizes["moe.w_gate"] + sizes["moe.w_up"] + sizes["moe.w_down"]
    assert shapes.expert_bytes(model) == 18_874_368
    # what a sequence keeps: a row a Mamba layer, K and V in the one
    # attention layer
    sm = cfg.serve_model()
    (tail, tdt), (state, sdt) = sm.state_pools(49, np.dtype("bfloat16"))
    row = int(np.prod(tail[2:])) * 2 + int(np.prod(state[2:])) * 4
    assert shapes.state_row_bytes(model) == row == 4_244_992
    assert shapes.kv_bytes_per_token(model) == sum(sm.stored) * 2 == 4096 \
        == family.kv_bytes_per_token(model)
    # a step of 48 rows at 150k live tokens: 13.8 GB, 16.8 ms at 819 GB/s
    # (9.51 of weights, 3.67 of state rows, 0.61 of K/V)
    slots = max(config["serve"]["decode_slots"])
    step = family.decode_step_min_bytes(model, 150_000.0)
    assert step == shapes.decode_step_min_bytes(model, 150_000.0, slots)
    weights = 2 * shapes.param_count(model)
    assert step == pytest.approx(
        weights - 2 * 10 * (36 - shapes.expected_experts_hit(model, 48))
        * shapes.expert_params(model)
        + 9 * 48 * 2 * row + 150_000 * 4096)
    assert 13.7e9 < step < 13.9e9
    assert 35.9 < shapes.expected_experts_hit(model, 48) < 36
    # the scopes' shares of it
    assert shapes.ssm_step_min_bytes(model, 48) == 9 * (
        2 * shapes.mamba_params(model) + 48 * 2 * row)
    assert shapes.mlp_min_bytes(model, 360.0) == 2 * (
        10 * (shapes.router_params(model) + shapes.shared_params(model))
        + 360 * shapes.expert_params(model))


# -- the readers -------------------------------------------------------------

NEW = ("ssm_share.granite4", "ssm_update_roofline.granite4",
       "moe_share.granite4", "held_swiglu_expert_roofline",
       "gqa_attention_roofline.granite4", "held_pair_share.granite4",
       "held_expert_load_max_over_mean.granite4",
       "state_rows_used_share.granite4", "window_admissions.granite4",
       "sessions_ready_s.granite4", "stream_silence_share.granite4")
OWN = ("held_swiglu_expert_roofline",
       "held_expert_load_max_over_mean.granite4")


def _records(model, steps, live=160000.0):
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    return {
        "kind": "serve", "model": model, "window_s": 40.0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": live,
                  "modules": {decode: {"count": 100, "median_s": 0.02},
                              prefill: {"count": 5, "median_s": 0.1}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 2.0, "by_scope": {
                "mlp": 1.0, "ssm": 0.7, "attention": 0.1, "qkv": 0.03,
                "head": 0.07, "layers.other": 0.1}},
            prefill: {"total_s": 0.5, "by_scope": {
                "mlp": 0.3, "ssm": 0.1, "attention": 0.05}}}},
        "program": {"steps": steps},
        "sessions": {"ready_s": 14.5, "silence_s": 0.4}}


def _steps():
    return [{"kind": "decode", "slots": 48, "experts_hit": 360 - i % 2,
             "expert_load_max": 14 + i % 3, "held_pairs": 2400,
             "zero_pairs": 0, "pairs": 4800, "state_rows": 48,
             "state_rows_used": 48} for i in range(10)]


def test_the_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    assert read("ssm_share.granite4")(rec) == pytest.approx(0.35)
    # 9 x (204.6 MB of weights + 48 rows x 2 x 4.24 MB) = 5.51 GB: 6.7 ms
    # at 819 GB/s, against 0.7 s / 100 steps = 7 ms under `ssm`
    assert read("ssm_update_roofline.granite4")(rec) == pytest.approx(
        100 * shapes.ssm_step_min_bytes(model, 48) / 819e9 / 0.007)
    assert 90 < read("ssm_update_roofline.granite4")(rec) < 100
    assert read("moe_share.granite4")(rec) == pytest.approx(0.5)
    # 359.5 held experts x 18.87 MB + 10 x (router + shared) = 7.17 GB:
    # 8.8 ms, against 1.0 s / 100 steps = 10 ms under `mlp`
    assert read("held_swiglu_expert_roofline")(rec) == pytest.approx(
        100 * shapes.mlp_min_bytes(model, 359.5) / 819e9 / 0.010)
    assert 80 < read("held_swiglu_expert_roofline")(rec) < 95
    # 160000 tokens x 4096 B = 0.66 GB: 0.8 ms, against 1 ms
    assert read("gqa_attention_roofline.granite4")(rec) == pytest.approx(
        100 * 160000 * 4096 / 819e9 / 0.001)
    assert read("held_pair_share.granite4")(rec) == pytest.approx(0.5)
    assert read("held_expert_load_max_over_mean.granite4")(rec) \
        == pytest.approx(np.mean([14 + i % 3 for i in range(10)])
                         / (2400 / 360))
    assert read("state_rows_used_share.granite4")(rec) == 1.0
    assert read("window_admissions.granite4")(rec) == 0
    assert read("sessions_ready_s.granite4")(rec) == 14.5
    assert read("stream_silence_share.granite4")(rec) == pytest.approx(0.01)


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """The parent's program, or another family's records: the metric is
    left out, and nothing raises."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    longcat = {"hidden": 6144, "layers": 4, "expert_dim": 2048,
               "n_experts": 512, "zero_experts": 256, "top_k": 12,
               "held": [0, 16], "vocab_size": 16384}
    plain = [{"kind": "decode", "slots": 16} for _ in range(5)]
    for name in OWN:
        assert read(name)(_records(olmoe, _steps())) is None, name
        assert read(name)(_records(longcat, _steps())) is None, name
        assert read(name)(_records(config["model"], plain)) is None, name
    for name in NEW:
        for broken in ({"kind": "serve"}, {"kind": "train"},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None),
                       dict(rec, trace=None), dict(rec, sessions=None)):
            read(name)(broken)                      # and nothing raises
    # LongCat's reader of the same stem leaves this family's records alone
    assert read("held_expert_load_max_over_mean")(rec) is None
    # a program whose ops carry no `ssm` scope (the parent's reduction)
    bare = _records(config["model"], _steps())
    for prog in bare["scopes"]["programs"].values():
        del prog["by_scope"]["ssm"]
    assert read("ssm_share.granite4")(bare) is None
    assert read("ssm_update_roofline.granite4")(bare) is None


# -- a tiny traced rehearsal through the sessions kind -----------------------


def _tiny_cell():
    from paddle_tpu.models import granite_hybrid

    tiny = dataclasses.asdict(granite_hybrid.GraniteHybridConfig.tiny())
    config = {
        "name": "tiny_granite", "family": "granite_hybrid",
        "model": dict(tiny, max_len=128, held=list(tiny["held"]),
                      logits_scaling=2.0),
        "reduced": ["max_position_embeddings"],
        "reduced_why": {"max_position_embeddings": "131072 -> 128 (the "
                        "tests')"},
        "serve": {"precision": "f32", "block_size": 8, "decode_slots": [4],
                  "kv_context_per_slot": 128, "eos_id": None,
                  "max_queue": 64},
        "logit_gap_tol": 0.001}
    mix = {"kind": "sessions", "loop": "closed", "clients": 4,
           "table_size": 4, "context_per_slot": 32768, "weights_seed": 7,
           "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 60},
           # a tiny model decodes a thousand tokens a second on the CPU
           "output_len": {"dist": "fixed", "value": 32000},
           "prefill_buckets": [32, 64], "lead_s": 4.0}
    return {"name": CELL, "chips": 1, "config_file": config,
            "traffic_file": mix}


def test_a_tiny_traced_rehearsal_is_correct_and_reports_the_new_metrics(
        tmp_path, monkeypatch, jax_cache_config):
    """Four sessions of a tiny model through the real engine, server and
    load generator: prompts walked in slices of 8 during the lead, every
    row decoding all through the window, the float32 engine's tokens the
    reference's own, the step records with the held experts' counters, and
    the line with the metrics that need no device trace."""
    from tests.benchmarks.test_benchmark_program_trace import _scopes

    monkeypatch.setattr(sessions, "TRACE_S", 0.3)
    monkeypatch.setattr(sessions.program_trace, "reduce_scopes",
                        lambda path: _scopes())
    args = types.SimpleNamespace(seed=2 ** 31 + 29, seconds=2.0, trace=1,
                                 rate=None, t_start=time.monotonic(),
                                 workload=CELL)
    res = sessions.run(copy.deepcopy(_tiny_cell()), args, str(tmp_path),
                       allow_cpu=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert (res["attempted"], res["failed"]) == (4, 0)
    assert checks["compared"]["window_admissions"] == [0, 0]
    assert checks["compared"]["ref_max_logit_gap"][0] <= 0.001
    mem = checks["memory"]
    # K and V of the ONE attention layer, 2 K/V heads of 16, float32 here
    assert mem["kv_bytes_per_token"] == 2 * 32 * 2
    steps = [s for s in res["records"]["program"]["steps"]
             if s["kind"] == "decode"]
    assert steps and all(
        s["pairs"] == 4 * 3 * 4 and 0 < s["held_pairs"] < s["pairs"]
        and s["zero_pairs"] == 0 and s["state_rows_used"] == 4
        for s in steps if "pairs" in s)
    line = json.loads(json.dumps(bench_run.emit(
        manifest.load_manifest(), args, res)))
    got = line["metrics"]
    assert 0.2 < got["held_pair_share.granite4"]["value"] < 0.8
    assert got["held_expert_load_max_over_mean.granite4"]["value"] >= 1.0
    assert got["state_rows_used_share.granite4"]["value"] == 1.0
    assert got["window_admissions.granite4"]["value"] == 0.0
    assert got["sessions_ready_s.granite4"]["value"] > 0
    assert {"stream_silence_share.granite4", "slot_occupancy",
            "engine_step_p50_ms.tput", "kv_block_used_share.tput",
            "setup_engine_warm_s.sessions", "setup_compile_s"} <= set(got)


def test_the_served_set_is_the_float32_one_rounded_once():
    import jax.numpy as jnp

    cfg = family.make_config(_tiny_cell()["config_file"]["model"])
    served, axes = family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = family.init(cfg, SEED)
    assert set(axes) == set(served)
    for k, v in f32.top.items():
        assert v.dtype == jnp.float32 and served[k].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(v.astype(jnp.bfloat16)),
                              np.asarray(served[k])), k
    seen = {}
    for b, kind in enumerate(ref_mod.blocks(cfg.pattern)):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        layer = f32.layer(b)
        assert ("blk.in_proj" in layer) == (kind == "M")
        assert ("blk.router" in layer) == (kind == "E")
        assert ("blk.wq" in layer) == (kind == "*")
        for k, v in layer.items():
            got = np.asarray(served[ref_mod.PREFIX[kind] + k[4:]][nth]
                             .astype(jnp.float32))
            want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
            assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
            assert (got != want).mean() < 1e-3, k


# -- what the tolerance tells apart, at the published widths -----------------

T = 48
CUT = {"pattern": "M*", "n_experts": 16, "held": [0, 8],
       "vocab_size": 12544}


@pytest.fixture(scope="module")
def published(config):
    """The published widths, layers `M*` of the pattern (blocks `ME*E`),
    with the experts (8 held of 16; still top-10) and the vocabulary (an
    eighth) cut for the CPU, one sequence of 48 seeded tokens: the
    PROGRAM's pick at every position (its full forward pass in bf16 from
    the served set; prefill and decode steps make the same pick from the
    same prefix, tests/test_granite_hybrid.py) is judged as the kind judges
    a streamed token: how far it lies, in the reference's float32 logits,
    below the reference's own argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import granite_hybrid

    model = dict(config["model"], **CUT)
    cfg = family.make_config(model)
    served, _ = family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = family.init(cfg, SEED)
    ids = jnp.asarray(traffic.prompt_ids(SEED, 0, T, model["vocab_size"]),
                      jnp.int32)
    picks = np.asarray(jax.jit(lambda p, i: granite_hybrid.apply(p, cfg, i))(
        served, ids[None])[0].argmax(-1))
    layers = [f32.layer(b) for b in range(2 * len(model["pattern"]))]
    return config, model, f32.top, layers, ids, picks


def _gap(published, model=None, weights=None):
    """`weights(name, value)`: a control on the reference's parameters."""
    import jax

    config, right, top, layers, ids, picks = published
    model = model or right
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, v) for k, v in top.items()}
    order = ref_mod.blocks(model["pattern"])
    steps = {kind: jax.jit(lambda lp, x, kind=kind: ref_mod.block(
        lp, x, model, kind, model.get("prompt_len"))) for kind in set(order)}
    with jax.default_matmul_precision("highest"):
        x = ref_mod.embed(top, model, ids)
        for lp, kind in zip(layers, order):
            x = steps[kind]({k: weights(k, v) for k, v in lp.items()}, x)
        rows = np.asarray(ref_mod.head_rows(top, model, x, 0, T))
    return ref_mod.verdict(rows.max(-1) - rows[np.arange(T), picks])


def test_the_bf16_program_is_within_the_tolerance(published):
    assert _gap(published) <= published[0]["logit_gap_tol"] / 2


@pytest.mark.parametrize("fault, switch", [
    ("embedding_multiplier_dropped", {"embedding_multiplier": 1.0}),
    ("rotary_positions_applied", {"rope": True}),
    ("B_and_C_taken_a_head", {"bc_per_head": True}),
    ("D_dropped", {"skip_D": True}),
    ("dt_bias_left_out", {"dt_bias": False}),
    ("shared_expert_dropped", {"shared_expert": False}),
    ("silu_gate_dropped", {"act": "none"}),
    ("held_term_dropped", {"held_term": False}),
    ("stale_state_row", {"stale_state": 64})])
def test_the_tolerance_fails_a_fault(published, fault, switch):
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], **switch)) > tol, fault


@pytest.mark.parametrize("fault, switch", [
    ("residual_multiplier_taken_as_1", {"residual_multiplier": 1.0}),
    ("softmax_at_rsqrt_128", {"attention_multiplier": 128 ** -0.5}),
    ("gated_norm_in_8_groups", {"norm_groups": 8}),
    ("conv_bias_dropped", {"conv_bias": False}),
    ("kept_weights_not_renormalised", {"norm_topk": False}),
    ("padded_tail_advances_the_state", {"pad_tail": 24, "prompt_len": 24})])
def test_a_fault_the_cut_hides_still_moves_a_pick(published, fault, switch):
    """Faults that read UNDER the tolerance at this cut (two layers, 48
    tokens of context, the top-10 of 16 experts holding 0.9 of the softmax
    where the top-10 of 72 hold 0.45) and over it at the cell's own size on
    the chip, all but the softmax's scale (`logit_gap_tol_reason` has each
    reading; tests/test_granite_hybrid.py holds every one of them on
    float32 logits): here each still puts some token off the reference's
    argmax, so the switch is wired."""
    assert _gap(published, dict(published[1], **switch)) > 0.0, fault


def test_what_the_tolerance_does_not_tell_apart(published):
    """Held so that nobody reads the tolerance as a guard of either:
    `logits_scaling` (every logit over 16 moves no argmax and scales both
    sides of a gap alike: a fault's reading with the scaling dropped is 16
    times its reading with it, and the program's picks are the same;
    tests/test_granite_hybrid.py compares the logits themselves), and a
    bf16 SSM state (Mamba-2's own draws forget within tens of tokens:
    `configs/nemotron3_nano.json`)."""
    tol = published[0]["logit_gap_tol"]
    fault = dict(published[1], shared_expert=False)
    assert _gap(published, dict(fault, logits_scaling=1.0)) == pytest.approx(
        16 * _gap(published, fault), rel=1e-3)
    assert _gap(published, dict(published[1], state_dtype="bfloat16")) \
        <= tol / 2


def test_float8_weights_are_not_correct(published):
    """The nearest precision below the stated one: the reference with its
    matrices rounded to float8 (e4m3) is over the tolerance, the same
    matrices rounded to bf16, which is what the program serves, under it."""
    import jax.numpy as jnp

    def rounded(dtype):
        return lambda k, v: v.astype(dtype).astype(jnp.float32) \
            if v.ndim >= 2 and k != "blk.conv_w" else v

    tol = published[0]["logit_gap_tol"]
    assert _gap(published, weights=rounded(jnp.float8_e4m3fn)) > tol
    assert _gap(published, weights=rounded(jnp.bfloat16)) <= tol / 2
