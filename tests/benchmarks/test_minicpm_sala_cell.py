"""The cell `minicpm_sala.longdoc_sessions` off the chip: its configuration
file against its source's keys (it differs in `reduced` and nowhere else),
the cell found with its readers and the traffic ISSUE 43 gives, its byte
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
from benchmarks.families import minicpm_sala as family
from benchmarks.harness import manifest, minicpm_sala_shapes as shapes
from benchmarks.kinds import sessions
from tests.benchmarks.test_nemotron_cell import jax_cache_config  # noqa: F401

CELL = "minicpm_sala.longdoc_sessions"

PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
                    + ["lightning-attn"] * 6 + ["minicpm4"] * 2
                    + ["lightning-attn"] * 4 + ["minicpm4"]
                    + ["lightning-attn"] * 6 + ["minicpm4"] * 3),
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "minicpm_sala.json")) as f:
        return json.load(f)


# -- the files ---------------------------------------------------------------


def test_the_configuration_is_the_source_but_for_what_it_lists(config):
    from paddle_tpu.models import minicpm_sala

    assert len(PUBLISHED["mixer_types"]) == 32
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"]) \
        == {"num_hidden_layers", "mixer_types", "max_position_embeddings"}
    # a contiguous stage of the published list at the published 1 : 3
    assert config["mixer_types"] == PUBLISHED["mixer_types"][9:17]
    assert config["num_hidden_layers"] == 8 == len(config["model"]["mixers"])
    assert config["model"]["mixers"] == "".join(
        "S" if m == "minicpm4" else "L" for m in config["mixer_types"])
    assert minicpm_sala.PUBLISHED_MIXERS == "".join(
        "S" if m == "minicpm4" else "L" for m in PUBLISHED["mixer_types"])
    assert config["model"]["mixers"].count("S") * 3 \
        == config["model"]["mixers"].count("L")
    for ours, theirs in config["source_keys"].items():
        assert config["model"][ours] == config[theirs], ours
    assert config["reduced_why"]["max_position_embeddings"].startswith(
        "524288 -> 49152")
    assert set(config["assumed"]) >= {
        "sparse_config", "slopes", "rotary", "state_dtype",
        "attention_scores", "output_gate_and_norm", "init"}
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "minicpm_sala")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/minicpm_sala.json"
    # no width is cut
    assert not [k for k in config["reduced"]
                if k.endswith(("_size", "_dim", "_rank", "_heads", "_nh"))]
    assert config["logit_gap_tol_reason"] != "TO BE SET FROM CHIP READINGS"
    assert config["deployment"]
    # the program's defaults are the published model
    full = dataclasses.asdict(minicpm_sala.MiniCPMSALAConfig())
    for key, value in config["model"].items():
        if key not in ("mixers", "max_len"):
            assert full[key] == value, key
    assert full["max_len"] == 524288


def test_the_cell_is_found_with_its_readers_and_the_issues_traffic(config):
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    mix = cell["traffic_file"]
    assert cell["chips"] == 1 and mix["kind"] == "sessions"
    assert mix["clients"] == mix["table_size"] == 32
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 16384,
                                 "hi": 32768}
    assert mix["output_len"] == {"dist": "fixed", "value": 16384}
    assert mix["prefill_buckets"] == [16384, 20480, 24576, 28672, 32768]
    assert mix["context_per_slot"] == 49152 and mix["weights_seed"] == 20261002
    assert mix["lead_s"] % 5 == 0
    served = sessions.with_context(cell["config_file"], mix)
    serve = served["serve"]
    assert serve["kv_context_per_slot"] == 49152 == served["model"]["max_len"]
    assert serve["decode_slots"] == [32] == [mix["clients"]]
    assert serve["state"]["rows"] == 33
    # a selection block is whole cache blocks, and a slice whole blocks
    model = served["model"]
    assert model["sel_block"] % serve["block_size"] == 0
    assert all(b % model["prompt_slice"] == 0
               for b in mix["prefill_buckets"])
    # every session is over `dense_len` from its first token on
    assert mix["prompt_len"]["lo"] > model["dense_len"]
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    new = {"sparse_attention_roofline": "decode kernels",
           "sparse_attention_share": "decode kernels",
           "sparse_select_share": "decode kernels",
           "sparse_rows_share": "decode kernels",
           "linear_state_roofline": "recurrent layers",
           "linear_attention_share": "recurrent layers",
           "dense_mlp_roofline": "models and XLA kernels",
           "state_rows_used_share.minicpm_sala": "decode engine",
           "window_admissions.minicpm_sala": "entry",
           "sessions_ready_s.minicpm_sala": "boot",
           "stream_silence_share.minicpm_sala": "entry"}
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
    # nothing of another family, and nothing that reads a prefill in the
    # window, is listed for it
    assert not {n for n in per_layer if n.split(".")[0] in (
        "prefill_gap_share", "engine_prefill_share", "ssm_scan_roofline",
        "ssm_share", "ssm_update_roofline", "gqa_attention_roofline",
        "moe_share", "relu2_expert_roofline", "setup_train_build_s")}
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] != "TBD" and len(entry["why"]) <= 200
    # where the byte counts' slot count comes from (families/minicpm_sala.py)
    assert inspect.signature(shapes.decode_step_min_bytes).parameters[
        "slots"].default == max(serve["decode_slots"])


def test_the_byte_counts_follow_the_programs_shapes(config):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import minicpm_sala
    from paddle_tpu.serving.kv_cache import KVCacheConfig

    model = config["model"]
    cfg = family.make_config(model)
    made = jax.eval_shape(lambda k: minicpm_sala.init(k, cfg)[0],
                          jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in made.values())
    assert shapes.param_count(model) == n
    assert 2 * n == pytest.approx(5.641e9, rel=1e-3)
    # the issue's arithmetic, layer by layer
    assert shapes.sparse_layer_params(model) == pytest.approx(253.8e6,
                                                              rel=1e-3)
    assert shapes.lightning_layer_params(model) == pytest.approx(285.2e6,
                                                                 rel=1e-3)
    assert shapes.top_params(model) == pytest.approx(601.7e6, rel=1e-3)
    full = dict(model, mixers=minicpm_sala.PUBLISHED_MIXERS)
    assert shapes.param_count(full) == pytest.approx(9.48e9, rel=0.01)
    # what a sequence holds, as the engine's own geometry says
    sm = cfg.serve_model()
    serve = config["serve"]
    kv = KVCacheConfig(layers=sm.kv_layers, widths=sm.stored,
                       max_len=49152, block_size=serve["block_size"],
                       num_blocks=32 * 768 + 1, rated=sm.rated)
    assert family.kv_bytes_per_token(model) == 2112 \
        == sm.kv_layers * kv.bytes_per_token()
    assert kv.pool_bytes() == pytest.approx(3.32e9, rel=1e-2)
    assert serve["stored"] == {
        "k": sm.stored[0], "v": sm.stored[1],
        "compressed_key": {"width": sm.rated[0][0],
                           "stride": sm.rated[0][1]}}
    (shape, dt), = sm.state_pools(33, jnp.bfloat16)
    assert shape[:2] == (6, 33) and dt == jnp.float32
    assert shapes.state_row_bytes(model) == 2097152 \
        == int(np.prod(shape[2:])) * 4
    # a 32-row step at 30k tokens a row: 6.2 GB, the weights 5.0 of them,
    # the state 0.81, the sparse read 0.33
    least = family.decode_step_min_bytes(model, 32 * 30000.0)
    assert least == pytest.approx(6.2e9, rel=0.02)
    assert shapes.always_read_bytes(model) == pytest.approx(5.04e9, rel=0.01)
    assert shapes.linear_state_min_bytes(model, 32) == pytest.approx(
        6 * (83.9e6 * 2 + 32 * 2 * 2097152), rel=1e-3)
    sparse = shapes.sparse_attention_min_bytes(
        model, 32 * (30000 // 16 - 1), 32, 64, 0)
    assert sparse == pytest.approx(
        2 * 32 * (1874 * 512 + 63.5 * 64 * 1024), rel=1e-6)
    assert sparse == pytest.approx(0.33e9, rel=0.03)
    # under `dense_len` a row reads every token it holds
    assert shapes.decode_step_min_bytes(model, 32 * 4000.0) \
        - shapes.decode_step_min_bytes(model, 32 * 2000.0) \
        == pytest.approx(2 * 32 * 2000 * 1024)
    assert shapes.dense_mlp_min_bytes(model) == 8 * 3 * 4096 * 16384 * 2


# -- the readers -------------------------------------------------------------

NEW = ("sparse_attention_roofline", "sparse_attention_share",
       "sparse_select_share", "sparse_rows_share", "linear_state_roofline",
       "linear_attention_share", "dense_mlp_roofline")


def _records(model, steps, scopes=("select", "kc_write", "ssm")):
    """A traced run's records; `scopes`: which of this family's scopes the
    program's ops carry (the parent's and the other families' carry no
    `select` or `kc_write`)."""
    decode, prefill = "jit__decode_fn", "jit__prefill_fn"
    rec = _all_records(model, steps, decode, prefill)
    for prog in rec["scopes"]["programs"].values():
        for scope in {"select", "kc_write", "ssm"} - set(scopes):
            prog["by_scope"].pop(scope, None)
    return rec


def _all_records(model, steps, decode, prefill):
    return {
        "kind": "serve", "model": model,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "trace": {"live_tokens_mean": 960000.0,
                  "modules": {decode: {"count": 100, "median_s": 0.01},
                              prefill: {"count": 5, "median_s": 0.1}}},
        "scopes": {"scoped_ops": 5000, "programs": {
            decode: {"total_s": 1.0, "by_scope": {
                "mlp": 0.5, "ssm": 0.2, "attention": 0.06, "select": 0.03,
                "kc_write": 0.01, "qkv": 0.03, "head": 0.1,
                "layers.other": 0.07}},
            prefill: {"total_s": 0.5, "by_scope": {
                "mlp": 0.3, "ssm": 0.1, "attention": 0.05}}}},
        "program": {"steps": steps}}


def _steps(sparse=32):
    return [{"kind": "decode", "slots": 32, "live": 32,
             "sparse_rows": sparse, "blocks_selected": 64.0,
             "dense_tokens": (32 - sparse) * 5000,
             "kc_entries": sparse * 1874, "state_rows": 32,
             "state_rows_used": 32} for _ in range(10)]


def test_the_new_readers_on_records_made_by_hand(config):
    model = config["model"]
    rec = _records(model, _steps())
    read = manifest.layer_metric_reader
    # the scopes nest: the attention's seconds are the three together
    assert read("sparse_attention_share")(rec) == pytest.approx(0.10)
    assert read("sparse_select_share")(rec) == pytest.approx(0.3)
    assert read("sparse_rows_share")(rec) == 1.0
    assert read("linear_attention_share")(rec) == pytest.approx(0.2)
    # 0.33 GB over 819 GB/s = 0.40 ms, against 0.1 s / 100 steps = 1 ms
    want = shapes.sparse_attention_min_bytes(model, 32 * 1874, 32, 64.0, 0)
    assert read("sparse_attention_roofline")(rec) == pytest.approx(
        100 * want / 819e9 / 0.001)
    assert 35 < read("sparse_attention_roofline")(rec) < 45
    # 6 x (168 MB + 32 x 2 x 2.1 MB) = 1.81 GB: 2.2 ms against 2 ms: a
    # trace that says so has left part of the work out of `ssm`
    assert read("linear_state_roofline")(rec) == pytest.approx(
        100 * shapes.linear_state_min_bytes(model, 32) / 819e9 / 0.002)
    # 3.22 GB: 3.9 ms, against 5 ms under `mlp`
    assert read("dense_mlp_roofline")(rec) == pytest.approx(
        100 * 8 * 3 * 4096 * 16384 * 2 / 819e9 / 0.005)
    assert 75 < read("dense_mlp_roofline")(rec) < 82
    # some rows at or under `dense_len`: the gate is partly shut
    mixed = _records(model, _steps(sparse=24))
    assert read("sparse_rows_share")(mixed) == 0.75
    assert read("sparse_attention_roofline")(mixed) == pytest.approx(
        100 * shapes.sparse_attention_min_bytes(
            model, 24 * 1874, 24, 64.0, 8 * 5000) / 819e9 / 0.001)
    # the kind's and the engine's readers serve the cell under their twins
    rec["sessions"] = {"ready_s": 31.5, "silence_s": 0.0}
    rec["window_s"] = 40.0
    assert read("state_rows_used_share.minicpm_sala")(rec) == 1.0
    assert read("window_admissions.minicpm_sala")(rec) == 0
    assert read("sessions_ready_s.minicpm_sala")(rec) == 31.5
    assert read("stream_silence_share.minicpm_sala")(rec) == 0.0


def test_the_readers_find_nothing_where_there_is_nothing_to_read(config):
    """The parent's program and the other families': no `mixers` in the
    model group, no `select` or `ssm` scope on any op, step records without
    the counters, no trace: the metric is left out, nothing raises."""
    read = manifest.layer_metric_reader
    rec = _records(config["model"], _steps())
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "nemotron3_nano.json")) as f:
        nemotron = json.load(f)["model"]
    olmoe = {"hidden": 2048, "layers": 8, "expert_dim": 1024,
             "n_experts": 64, "top_k": 8, "vocab_size": 50304}
    plain = [{"kind": "decode", "slots": 16, "live": 16} for _ in range(5)]
    for name in NEW:
        for other, scopes in ((olmoe, ()), (nemotron, ("ssm",))):
            assert read(name)(_records(other, plain, scopes)) is None, name
        if name != "sparse_rows_share":     # a counter: needs no trace
            assert read(name)(dict(rec, trace=None, scopes=None)) is None, \
                name
        for broken in ({"kind": "serve"}, {"kind": "train"}, {},
                       dict(rec, scopes=None), dict(rec, model=None),
                       dict(rec, program=None), dict(rec, peaks=None)):
            read(name)(broken)                      # and nothing raises
    # the parent's program serving this model's records' shape: its ops
    # carry no `select`, `kc_write` or `ssm` scope and its steps no counter
    bare = _records(config["model"], plain, scopes=())
    for name in ("sparse_attention_roofline", "sparse_attention_share",
                 "sparse_select_share", "sparse_rows_share",
                 "linear_state_roofline", "linear_attention_share"):
        assert read(name)(bare) is None, name


def test_the_family_registers_its_scopes_with_the_reduction(config):
    """`select` and `kc_write` are no scopes of the harness's own list (a
    file this PR may not edit); building this family's model makes them
    scopes, innermost first: an op under attention/select is `select`'s."""
    from benchmarks.harness import program_trace

    cfg = family.make_config(dict(config["model"], dense_walk=True))
    assert not hasattr(cfg, "dense_walk")   # the reference's switch alone
    family.register_scopes()                # idempotent
    for scope in ("ssm", "select", "kc_write"):
        assert program_trace.SCOPES.count(scope) == 1
    assert program_trace.COMPUTE.count("select") == 1
    assert "kc_write" not in program_trace.COMPUTE
    at = "jit(_decode_fn)/jit(main)/layers/attention/"
    assert program_trace.scope_of(at + "select/top_k") == "select"
    assert program_trace.scope_of(at + "kc_write/scatter") == "kc_write"
    assert program_trace.scope_of(at + "pallas_call") == "attention"
    assert program_trace.scope_of(
        "jit(_decode_fn)/jit(main)/layers/ssm/scan/pallas_call") == "ssm"
    assert set(family.ATTENTION_SCOPES) == {"attention", "select",
                                            "kc_write"}


# -- a tiny traced rehearsal through the sessions kind -----------------------


def _tiny_cell():
    from paddle_tpu.models import minicpm_sala

    tiny = dataclasses.asdict(minicpm_sala.MiniCPMSALAConfig.tiny())
    config = {
        "name": "tiny_sala", "family": "minicpm_sala",
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
    load generator: every row decodes over `dense_len` (48) all through the
    window, the float32 engine's tokens are the reference's own (a gap of
    0), the step records carry the sparse read's counters, and the line has
    the metrics that need no device trace."""
    from tests.benchmarks.test_benchmark_program_trace import _scopes

    monkeypatch.setattr(sessions, "TRACE_S", 0.3)
    monkeypatch.setattr(sessions.program_trace, "reduce_scopes",
                        lambda path: _scopes())
    args = types.SimpleNamespace(seed=2 ** 31 + 23, seconds=2.0, trace=1,
                                 rate=None, t_start=time.monotonic(),
                                 workload=CELL)
    cell = _tiny_cell()
    res = sessions.run(copy.deepcopy(cell), args, str(tmp_path),
                       allow_cpu=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert (res["attempted"], res["failed"]) == (4, 0)
    assert checks["compared"]["window_admissions"] == [0, 0]
    assert checks["compared"]["ref_max_logit_gap"][0] <= 0.05
    assert min(checks["sampled_context"]) > 48
    mem = checks["memory"]
    # K, V and the compressed key's share, both sparse layers, float32
    assert mem["kv_bytes_per_token"] == 2 * (2 * 32 + 32 // 4) * 2
    assert mem["kv_pool_bytes"] == 2 * (4 * 512 + 1) * (2 * 8 * 32 + 64) * 4
    steps = [s for s in res["records"]["program"]["steps"]
             if s["kind"] == "decode"]
    assert steps and all(s["sparse_rows"] == 4 == s["live"]
                         and s["blocks_selected"] == 4.0
                         and s["dense_tokens"] == 0 for s in steps)
    assert steps[-1]["kc_entries"] > steps[0]["kc_entries"] > 4 * 11
    bench = manifest.load_manifest()
    line = json.loads(json.dumps(bench_run.emit(bench, args, res)))
    got = line["metrics"]
    assert got["sparse_rows_share"] == {"value": 1.0, "unit": "share"}
    assert got["state_rows_used_share.minicpm_sala"]["value"] == 1.0
    assert got["window_admissions.minicpm_sala"]["value"] == 0.0
    assert got["sessions_ready_s.minicpm_sala"]["value"] > 0
    assert {"stream_silence_share.minicpm_sala", "slot_occupancy",
            "engine_step_p50_ms.tput", "kv_block_used_share.tput",
            "setup_engine_warm_s.sessions", "setup_compile_s"} <= set(got)
    # the stand-in reduction has no `select` or `ssm` seconds: the trace's
    # readers leave their metrics out
    assert not {"sparse_select_share", "linear_attention_share",
                "sparse_attention_roofline"} & set(got)
