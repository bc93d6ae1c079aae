"""The cell `trinity_mini.ctx32k_sessions` off the chip: its configuration
file against its source's keys (it differs in `reduced` and nowhere else),
the cell found with its readers and the traffic ISSUE 60 gives, its byte
counts against the parameters' own sizes, its readers on records made by
hand (and on the other families' records and the parent's program: nothing,
and no error), a tiny traced rehearsal through the `sessions` kind with both
kinds of pool, and what `logit_gap_tol` tells apart at the published widths
(layers `W*` of the pattern, one dense and one expert layer of 4 held of 8
experts, an eighth of the vocabulary, 48 tokens, for the CPU: what a window
of 2048 keys changes cannot show at 48 tokens and is tests/test_afmoe.py's,
at a window of 32, and the chip's, at the cell's own size)."""

import copy
import dataclasses
import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import afmoe as family
from benchmarks.harness import afmoe_shapes as shapes, manifest, traffic
from benchmarks.kinds import sessions
from benchmarks.reference import afmoe_ref as ref_mod
from tests.benchmarks.test_nemotron_cell import jax_cache_config  # noqa: F401

CELL = "trinity_mini.ctx32k_sessions"
SEED = 3000000037
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "trinity_mini.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------

PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def test_the_model_group_is_the_source_under_the_programs_names(config):
    """Every key of the source's config.json stands at the top level under
    its own name; `model` repeats the sizes under the program's names, and
    only the keys under `reduced` differ from the source."""
    if os.path.exists(CATALOG):     # the catalog's row is what was copied
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"])
    model = config["model"]
    assert config["num_hidden_layers"] == 8 == len(model["pattern"])
    # two whole periods: the published pattern's first eight entries
    assert config["layer_types"] == PUBLISHED["layer_types"][:8]
    assert model["pattern"] == "".join(
        {"sliding_attention": "W", "full_attention": "*"}[t]
        for t in config["layer_types"]) == "WWW*WWW*"
    assert config["num_experts"] == 64 == model["held"][1] - model["held"][0]
    assert model["n_experts"] == PUBLISHED["num_experts"]
    assert config["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    for ours, theirs in config["source_keys"].items():
        key = theirs.split(" ")[0]
        if ours in ("pattern", "held", "n_experts"):
            continue            # held above
        assert model[ours] == config[key], ours
    assert model["dense_layers"] == 2 <= len(model["pattern"]) - 4
    assert set(config["assumed"]) >= {
        "output_gate", "qk_norm", "rope_on_sliding_only", "four_norms",
        "expert_bias", "embedding_multiplier"}
    assert all("modeling_afmoe.py" in config["assumed"][k] for k in (
        "output_gate", "qk_norm", "rope_on_sliding_only", "four_norms",
        "expert_bias", "embedding_multiplier"))
    assert "2 chips share each layer x 4 pipeline stages" \
        in config["deployment"] and "6.32 GB" in config["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "trinity_mini")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # no width is cut
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_heads", "_tok",
                               "intermediate_size", "hidden_size"))]
    assert "PROVISIONAL" not in config["logit_gap_tol_reason"]
    # the program's configuration takes the group as it stands
    cfg = family.make_config(dict(model, rope_full=True, q_block=64))
    assert cfg.held == (0, 64) and cfg.count("W") == 6 \
        and cfg.count("E") == 8 and cfg.count("*") == 2
    sm = cfg.serve_model()
    assert (sm.kv_layers, sm.window_layers, sm.window) == (2, 6, 2048)


def test_the_cell_is_found_with_its_readers(config):
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    assert cell["chips"] == 1 and mix["kind"] == "sessions"
    assert 24 <= mix["clients"] == mix["table_size"] \
        == max(config["serve"]["decode_slots"]) \
        == shapes.decode_step_min_bytes.__defaults__[0] <= 32
    assert mix["prefill_buckets"] == [16384, 20480, 24576, 28672, 32768]
    assert all(b % config["model"]["prompt_slice"] == 0
               for b in mix["prefill_buckets"])
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 16384,
                                 "hi": 32768}
    assert mix["output_len"] == {"dist": "fixed", "value": 8192}
    assert mix["context_per_slot"] == 40960 \
        and mix["weights_seed"] == 20261005
    assert mix["lead_s"] >= 10 and mix["lead_s"] % 5 == 0
    assert "PROVISIONAL" not in mix["note"]
    served = sessions.with_context(cell["config_file"], mix)
    assert served["model"]["max_len"] == 40960 \
        == served["serve"]["kv_context_per_slot"]
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    for name, layer, moves in (
            ("window_attention_roofline.trinity", "decode kernels",
             "serve_tokens_per_s"),
            ("global_attention_roofline.trinity", "decode kernels",
             "serve_tokens_per_s"),
            ("held_expert_layer_roofline.trinity", "expert layer",
             "serve_tokens_per_s"),
            ("held_pair_share.trinity", "expert layer",
             "serve_tokens_per_s"),
            ("window_block_used_share.trinity", "decode engine",
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
    # the benchmark may hold 128 per-layer metrics: five are this cell's own
    # (no count is pinned: a later cell appends, and may not edit this file)
    assert len(bench["per_layer"]) <= 128
    assert len(bench["workloads"]) >= 14 and len(bench["configs"]) >= 11
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["bert_base.dp4"]


def test_the_byte_counts_follow_the_programs_shapes(config):
    """`afmoe_shapes` against the parameters' own sizes (shapes alone:
    nothing of 6.3 GB is made), the pools of both kinds against the
    model's."""
    import jax

    from paddle_tpu.models import afmoe
    from paddle_tpu.serving import kv_cache as kvc

    model = config["model"]
    cfg = family.make_config(model)
    params = jax.eval_shape(lambda k: afmoe.init(k, cfg)[0],
                            jax.random.key(0))
    sizes = {k: int(np.prod(v.shape)) for k, v in params.items()}
    assert shapes.param_count(model) == sum(sizes.values()) == 3_158_905_600

    def of(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    norms = 2 * model["hidden"]         # a block's pre- and post-norm
    assert (shapes.attention_params(model) + norms) * 6 == of("wattn.")
    assert (shapes.attention_params(model) + norms) * 2 == of("attn.")
    assert shapes.attention_params(model) == 27_263_232
    assert (shapes.dense_mlp_params(model) + norms) * 2 == of("dense.")
    assert (shapes.router_params(model) + 65 * shapes.expert_params(model)
            + norms) * 6 == of("moe.")
    assert shapes.top_params(model) == of("wte.") + of("ln_f.") + of("head.")
    assert shapes.expert_params(model) * 64 * 6 \
        == sizes["moe.w_gate"] + sizes["moe.w_up"] + sizes["moe.w_down"]
    assert 2 * shapes.expert_params(model) == 12_582_912
    # what a sequence keeps: every token in the 2 full layers, a ring in
    # the 6 sliding ones
    sm = cfg.serve_model()
    assert shapes.token_layer_bytes(model) == sum(sm.stored) * 2 == 2048
    assert shapes.kv_bytes_per_token(model) == 4096 \
        == family.kv_bytes_per_token(model)
    ring = kvc.ring_blocks(sm.window, sm.prompt_slice, 16)
    assert ring == 193 and ring * 16 * 6 * 2048 == 37_945_344
    # a step of 32 rows at 27k live tokens a session: 9.64 GB, 11.8 ms at
    # 819 GB/s
    slots = max(config["serve"]["decode_slots"])
    live = 32 * 27000.0
    step = family.decode_step_min_bytes(model, live)
    assert step == shapes.decode_step_min_bytes(model, live, slots)
    assert step == pytest.approx(
        shapes.always_read_bytes(model)
        + 6 * shapes.expected_experts_hit(model, 32) * 12_582_912
        + live * 4096 + 32 * 2048 * 6 * 2048)
    assert 9.5e9 < step < 9.8e9
    assert 55.8 < shapes.expected_experts_hit(model, 32) < 56.0
    assert shapes.window_min_bytes(model, 32 * 2048) == 32 * 2048 * 6 * 2048
    assert shapes.attention_min_bytes(model, live) == live * 4096
    assert shapes.mlp_min_bytes(model, 336.0) == 2 * (
        2 * shapes.dense_mlp_params(model)
        + 6 * (shapes.router_params(model) + shapes.expert_params(model))
        + 336 * shapes.expert_params(model))


# -- the readers -------------------------------------------------------------

NEW = ("window_attention_roofline.trinity",
       "global_attention_roofline.trinity",
       "held_expert_layer_roofline.trinity", "held_pair_share.trinity",
       "window_block_used_share.trinity")
OWN = ("window_attention_roofline.trinity",
       "global_attention_roofline.trinity",
       "held_expert_layer_roofline.trinity")


def _records(model, steps, live=864000.0):
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    return {
        "kind": "serve", "model": model, "window_s": 40.0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": live,
                  "modules": {decode: {"count": 100, "median_s": 0.015},
                              prefill: {"count": 5, "median_s": 0.1}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 1.5, "by_scope": {
                "mlp": 0.6, "window_attention": 0.15, "attention": 0.5,
                "qkv": 0.05, "head": 0.1, "layers.other": 0.1}},
            prefill: {"total_s": 0.5, "by_scope": {
                "mlp": 0.3, "attention": 0.05}}}},
        "program": {"steps": steps},
        "sessions": {"ready_s": 24.5, "silence_s": 0.4}}


def _steps():
    return [{"kind": "decode", "slots": 32, "experts_hit": 336 - i % 2,
             "expert_load_max": 5 + i % 3, "held_pairs": 768,
             "zero_pairs": 0, "pairs": 1536, "window_tokens": 32 * 2048,
             "window_blocks_used": 32 * 193, "window_blocks_usable": 32 * 193,
             "live_tokens": 864000} for i in range(10)]


def test_the_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    # 65536 keys x 6 layers x 2048 B = 0.81 GB: 0.98 ms at 819 GB/s,
    # against 0.15 s / 100 steps = 1.5 ms under `window_attention`
    assert read("window_attention_roofline.trinity")(rec) == pytest.approx(
        100 * 65536 * 6 * 2048 / 819e9 / 0.0015)
    # 864000 tokens x 4096 B = 3.54 GB: 4.3 ms, against 5 ms
    assert read("global_attention_roofline.trinity")(rec) == pytest.approx(
        100 * 864000 * 4096 / 819e9 / 0.005)
    assert read("held_expert_layer_roofline.trinity")(rec) == pytest.approx(
        100 * shapes.mlp_min_bytes(model, 335.5) / 819e9 / 0.006)
    assert 70 < read("held_expert_layer_roofline.trinity")(rec) < 100
    assert read("held_pair_share.trinity")(rec) == pytest.approx(0.5)
    assert read("window_block_used_share.trinity")(rec) == 1.0


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """The parent's program, or another family's records: the metric is
    left out, and nothing raises."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    granite = {"hidden": 4096, "pattern": "MMMMM*MMMM", "expert_dim": 768,
               "n_experts": 72, "top_k": 10, "held": [0, 36], "heads": 32,
               "kv_heads": 8, "head_dim": 128, "residual_multiplier": 0.22,
               "vocab_size": 50176}
    plain = [{"kind": "decode", "slots": 16} for _ in range(5)]
    for name in OWN:
        assert read(name)(_records(olmoe, _steps())) is None, name
        assert read(name)(_records(granite, _steps())) is None, name
    for name in ("window_attention_roofline.trinity",
                 "held_expert_layer_roofline.trinity",
                 "window_block_used_share.trinity"):
        assert read(name)(_records(config["model"], plain)) is None, name
    for name in NEW:
        for broken in ({"kind": "serve"}, {"kind": "train"},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None),
                       dict(rec, trace=None), dict(rec, sessions=None)):
            read(name)(broken)                      # and nothing raises
    # the other families' readers of the same stems leave these alone
    assert read("held_swiglu_expert_roofline")(rec) is None
    assert read("gqa_attention_roofline.granite4")(
        dict(rec, scopes=None)) is None
    # a program whose ops carry no `window_attention` scope (the parent's)
    bare = _records(config["model"], _steps())
    for prog in bare["scopes"]["programs"].values():
        prog["by_scope"].pop("window_attention", None)
    assert read("window_attention_roofline.trinity")(bare) is None


# -- a tiny traced rehearsal through the sessions kind -----------------------


def _tiny_cell():
    from paddle_tpu.models import afmoe

    tiny = dataclasses.asdict(afmoe.AfmoeConfig.tiny())
    config = {
        "name": "tiny_trinity", "family": "afmoe",
        "model": dict(tiny, max_len=256, held=list(tiny["held"]),
                      q_block=16, block=8),
        "reduced": ["max_position_embeddings"],
        "reduced_why": {"max_position_embeddings": "131072 -> 8192 (the "
                        "tests')"},
        "serve": {"precision": "f32", "block_size": 8, "decode_slots": [4],
                  "kv_context_per_slot": 256, "eos_id": None,
                  "max_queue": 64},
        "logit_gap_tol": 0.002}
    mix = {"kind": "sessions", "loop": "closed", "clients": 4,
           # off the chip a step gathers every slot's whole table: a context
           # short enough for 16 tokens a session in a window on a busy
           # machine, and longer than ten seconds of decoding on an idle one
           "table_size": 4, "context_per_slot": 8192, "weights_seed": 7,
           "prompt_len": {"dist": "loguniform", "lo": 40, "hi": 120},
           "output_len": {"dist": "fixed", "value": 8000},
           "prefill_buckets": [64, 128], "lead_s": 4.0}
    return {"name": CELL, "chips": 1, "config_file": config,
            "traffic_file": mix}


def test_a_tiny_traced_rehearsal_is_correct_and_reports_the_new_metrics(
        tmp_path, monkeypatch, jax_cache_config):
    """Four sessions of a tiny model (a window of 32) through the real
    engine, server and load generator: prompts of 40-120 tokens walked in
    slices of 16 during the lead, every row decoding all through the window
    far past its ring, the float32 engine's tokens the reference's own, the
    step records by kind, and the line with the metrics that need no device
    trace."""
    from tests.benchmarks.test_benchmark_program_trace import _scopes

    monkeypatch.setattr(sessions, "TRACE_S", 0.3)
    monkeypatch.setattr(sessions.program_trace, "reduce_scopes",
                        lambda path: _scopes())
    args = types.SimpleNamespace(seed=2 ** 31 + 29, seconds=3.0, trace=1,
                                 rate=None, t_start=time.monotonic(),
                                 workload=CELL)
    res = sessions.run(copy.deepcopy(_tiny_cell()), args, str(tmp_path),
                       allow_cpu=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert (res["attempted"], res["failed"]) == (4, 0)
    assert checks["compared"]["window_admissions"] == [0, 0]
    assert checks["compared"]["ref_max_logit_gap"][0] <= 0.002
    mem = checks["memory"]
    # K and V of the ONE full layer, 2 K/V heads of 16, float32 here
    assert mem["kv_bytes_per_token"] == 2 * 32 * 2
    # both kinds' pools: 1 layer x (4 x 1024 + 1) blocks and 3 layers x
    # (4 x 7 + 1) blocks of 8 tokens x 2 x 32 lanes x 4 B
    assert mem["kv_pool_bytes"] == (4097 + 3 * 29) * 8 * 64 * 4
    steps = [s for s in res["records"]["program"]["steps"]
             if s["kind"] == "decode"]
    assert steps and all(
        s["window_tokens"] == 4 * 32 and s["window_blocks_used"] == 4 * 7
        and s["window_blocks_usable"] == 28 and s["live_tokens"] > 4 * 60
        for s in steps)
    assert all(s["pairs"] == 4 * 3 * 3 and 0 < s["held_pairs"] < s["pairs"]
               for s in steps if "pairs" in s)
    line = json.loads(json.dumps(bench_run.emit(
        manifest.load_manifest(), args, res)))
    got = line["metrics"]
    assert 0.2 < got["held_pair_share.trinity"]["value"] < 0.8
    assert got["window_block_used_share.trinity"]["value"] == 1.0
    assert {"slot_occupancy",
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
    model = dataclasses.asdict(cfg)
    for b, kind in enumerate(ref_mod.blocks(cfg.pattern)):
        layer = f32.layer(b)
        assert ("blk.wg" in layer) == (kind in "W*")
        assert ("blk.router" in layer or "blk.mlp_gate" in layer) \
            == (kind == "E")
        want = ref_mod.layer_of(served, model, b)
        assert set(want) == set(layer)
        for k, v in layer.items():
            assert np.array_equal(np.asarray(v.astype(jnp.bfloat16)),
                                  np.asarray(want[k])), (b, k)
    # a control's uncut block holds every routed expert, the held among them
    b = 2 * cfg.dense_layers + 1
    whole, held = f32.whole(b), f32.layer(b)
    assert whole["blk.w_up"].shape[0] == cfg.n_experts
    assert np.array_equal(np.asarray(whole["blk.w_up"][:4]),
                          np.asarray(held["blk.w_up"]))


# -- what the tolerance tells apart, at the published widths -----------------

T = 48
CUT = {"pattern": "W*", "dense_layers": 1, "n_experts": 8, "held": [0, 4],
       "vocab_size": 12512, "q_block": 16}


@pytest.fixture(scope="module")
def published(config):
    """The published widths, layers `W*` of the pattern (blocks `WE*E`: a
    dense MLP, then 4 held of 8 experts, still top-8), an eighth of the
    vocabulary, one sequence of 48 seeded tokens: the PROGRAM's pick at
    every position (its full forward pass in bf16 from the served set;
    prefill and decode steps make the same pick from the same prefix,
    tests/test_afmoe.py) is judged as the kind judges a streamed token: how
    far it lies, in the reference's float32 logits, below the reference's
    own argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import afmoe

    model = dict(config["model"], **CUT)
    cfg = family.make_config(model)
    served, _ = family.init(cfg, SEED, dtype="bfloat16")
    f32, _ = family.init(cfg, SEED)
    ids = jnp.asarray(traffic.prompt_ids(SEED, 0, T, model["vocab_size"]),
                      jnp.int32)
    picks = np.asarray(jax.jit(lambda p, i: afmoe.apply(p, cfg, i))(
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
    step = jax.jit(lambda lp, x, kind: ref_mod.block(lp, x, model, kind),
                   static_argnums=2)
    with jax.default_matmul_precision("highest"):
        x = ref_mod.embed(top, model, ids)
        for lp, kind in zip(layers, order):
            x = step({k: weights(k, v) for k, v in lp.items()}, x, kind)
        rows = np.asarray(ref_mod.head_rows(top, model, x, 0, T))
    return ref_mod.verdict(rows.max(-1) - rows[np.arange(T), picks])


def test_the_bf16_program_is_within_the_tolerance(published):
    assert _gap(published) <= published[0]["logit_gap_tol"] / 2


@pytest.mark.parametrize("fault, switch", [
    ("rotary_dropped_from_the_sliding_ones", {"rope_sliding": False}),
    ("output_gate_dropped", {"output_gate": False}),
    ("qk_norm_dropped", {"qk_norm": False}),
    ("post_norms_dropped", {"post_norms": False}),
    ("embedding_multiplier_dropped", {"mup_enabled": False}),
    ("shared_expert_dropped", {"shared_expert": False}),
    ("held_term_dropped", {"held_term": False})])
def test_the_tolerance_fails_a_fault(published, fault, switch):
    tol = published[0]["logit_gap_tol"]
    assert _gap(published, dict(published[1], **switch)) > tol, fault


@pytest.mark.parametrize("fault, switch", [
    ("rotary_on_the_full_layers_too", {"rope_full": True}),
    ("route_scale_taken_as_1", {"route_scale": 1.0}),
    ("kept_weights_not_normalised", {"norm_topk": False})])
def test_a_fault_the_cut_hides_still_moves_picks(published, fault, switch):
    """Faults that read UNDER the tolerance at this cut (ONE full layer and
    ONE expert layer, 48 tokens of context, the top-8 of 8 experts: 0.47,
    0.73 and 0.41 against 1.0) and over it at the cell's own size on the
    chip (3.3, 6.0 and 8.9: `logit_gap_tol_reason` (3); tests/test_afmoe.py
    holds every one of them on float32 logits): here each still puts more
    than an eighth of the tokens off the reference's argmax, where the bf16
    program puts none, so the switch is wired."""
    assert _gap(published) == 0.0
    assert _gap(published, dict(published[1], **switch)) > 0.25, fault


def test_float8_weights_are_not_correct(published):
    """The nearest precision below the stated one: the reference with its
    matrices rounded to float8 (e4m3) is AT the tolerance through this
    cut's two layers (0.996 against 1.0; 8.05 and 8.95 through the cell's
    eight on the chip, `logit_gap_tol_reason` (2)), the same matrices
    rounded to bf16, which is what the program serves, read nothing."""
    import jax.numpy as jnp

    def rounded(dtype):
        return lambda k, v: v.astype(dtype).astype(jnp.float32) \
            if v.ndim >= 2 else v

    tol = published[0]["logit_gap_tol"]
    assert _gap(published, weights=rounded(jnp.float8_e4m3fn)) > 0.9 * tol
    assert _gap(published, weights=rounded(jnp.bfloat16)) <= tol / 10
