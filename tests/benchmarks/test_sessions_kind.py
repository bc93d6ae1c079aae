"""The `sessions` kind (`benchmarks/kinds/sessions.py`): a serve cell whose
window holds no admission. Its window arithmetic on records made by hand,
its one traffic key, its three readers, and tiny CPU rehearsals of the whole
runner through the program's real engine and server (the tiny `nemotron_h`
preset): a sound run is `correct`, and a lead that is too short, a session
that ends inside the window, a token altered where it is produced and a
token flipped before the window each come out `correct: false`."""

import copy
import json
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest
from benchmarks.kinds import sessions
from tests.benchmarks import sessions_control
from tests.benchmarks.test_nemotron_cell import (  # noqa: F401
    SEED, TINY as TINY_MODEL, config, jax_cache_config, published)

CELL = "nemotron3_nano.doc_sessions"
TINY = dict(
    TINY_MODEL, name="tiny_sessions", reduced=["max_position_embeddings"],
    reduced_why={"max_position_embeddings": "8192 -> 128 (the tests')"})
# four sessions on the preset's four slots; an output no CPU run reaches
TINY_MIX = {"kind": "sessions", "loop": "closed", "clients": 4,
            "table_size": 4, "context_per_slot": 4096, "weights_seed": 7,
            "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 60},
            "output_len": {"dist": "fixed", "value": 4000},
            "prefill_buckets": [16, 32, 64], "lead_s": 1.5}
READERS = ("window_admissions", "sessions_ready_s", "stream_silence_share")


# -- the one new key ---------------------------------------------------------


def test_context_per_slot_reaches_the_served_and_the_model_group():
    got = sessions.with_context(TINY, TINY_MIX)
    assert got["serve"]["kv_context_per_slot"] == 4096 \
        == got["model"]["max_len"]
    # the configuration's own file is left as it was, as are slots, block
    # size, precision and the rest of the served group
    assert TINY["model"]["max_len"] == 128
    assert {k: v for k, v in got["serve"].items()
            if k != "kv_context_per_slot"} == {
        k: v for k, v in TINY["serve"].items() if k != "kv_context_per_slot"}


@pytest.mark.parametrize("config, mix, match", [
    (dict(TINY, reduced=[]), TINY_MIX, "lists max_position_embeddings"),
    (dict(TINY, reduced_why={}), TINY_MIX, "states '<published>"),
    (dict(TINY, reduced_why={"max_position_embeddings": "cut to 128"}),
     TINY_MIX, "states '<published>"),
    (TINY, dict(TINY_MIX, context_per_slot=8193), "over the published"),
    (TINY, dict(TINY_MIX, context_per_slot=4000), "longest prompt"),
    (TINY, dict(TINY_MIX, clients=5, table_size=5), "slots"),
    (TINY, dict(TINY_MIX, table_size=8), "slots")])
def test_context_per_slot_is_refused_where_it_is_not_allowed(config, mix,
                                                             match):
    with pytest.raises(ValueError, match=match):
        sessions.with_context(config, mix)


def test_the_benchmarks_own_configurations_are_read_as_they_are_written():
    """`nemotron3_nano` lists the key and states 262144; the proving cell's
    9216 is under it, and `gpt2_large`, which reduces nothing, refuses."""
    bench = manifest.load_manifest()
    cell = manifest.find_cell(bench, CELL)
    got = sessions.with_context(cell["config_file"], cell["traffic_file"])
    assert got["model"]["max_len"] == 9216 \
        == got["serve"]["kv_context_per_slot"]
    mix = cell["traffic_file"]
    assert mix["clients"] == mix["table_size"] == 64 \
        == max(got["serve"]["decode_slots"])
    assert max(mix["prefill_buckets"]) == mix["prompt_len"]["hi"] == 3072
    assert mix["prompt_len"]["hi"] + mix["output_len"]["value"] == 9216
    with pytest.raises(ValueError, match="over the published"):
        sessions.with_context(cell["config_file"],
                              dict(mix, context_per_slot=262145))
    dense = manifest.find_cell(bench, "gpt2_large.doc_closed")["config_file"]
    with pytest.raises(ValueError, match="lists max_position_embeddings"):
        sessions.with_context(dense, mix)
    # the cell reports the rate, the set-up, and this kind's three readers
    assert {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"]: m for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}
    assert set(READERS) <= set(per_layer)
    for name in READERS:
        assert per_layer[name]["workloads"] == [CELL]
    assert per_layer["sessions_ready_s"]["moves"] == "setup_s"
    # nothing that reads a prefill inside the window is listed for it
    assert not {n for n in per_layer if n.split(".")[0] in (
        "prefill_gap_share", "engine_prefill_share", "ssm_scan_roofline")}


# -- the window's arithmetic on records made by hand -------------------------

W0, W1, T0 = 100.0, 140.0, 90.0


def _session(idx, first=92.0, last=150.0, step=0.5, **over):
    n = int((last - first) / step)
    times = [first + i * step for i in range(n)]
    return dict({"idx": idx, "due": T0, "sent": T0 + 0.01, "prompt_len": 8,
                 "asked": 4000, "token_times": times,
                 "tokens": [(7 * idx + i) % 500 for i in range(n)],
                 "done": False, "cut": True, "error": None}, **over)


def test_a_sound_window_is_ready_and_holds_no_admission():
    recs = [_session(i, first=92.0 + i) for i in range(4)]
    got = sessions.window_report(recs, 4, W0, W1, T0)
    assert got == {"sessions_ready": True, "window_admissions": 0,
                   "failed": [], "ready_s": pytest.approx(5.0)}


@pytest.mark.parametrize("fault, ready, admissions, failed", [
    ("first_token_in_the_window", False, 0, []),
    ("sent_in_the_window", False, 1, []),
    ("a_session_missing", False, 0, [3]),
    ("ended_and_sent_again", False, 2, [1]),
    ("errored", True, 0, [2]),
    ("no_token_in_the_window", True, 0, [0])])
def test_what_voids_a_window_or_fails_a_session(fault, ready, admissions,
                                                failed):
    recs = [_session(i) for i in range(4)]
    if fault == "first_token_in_the_window":
        recs[1] = _session(1, first=100.5)
    elif fault == "sent_in_the_window":
        recs[1] = _session(1, first=101.0, sent=100.2)
    elif fault == "a_session_missing":
        recs.pop()
    elif fault == "ended_and_sent_again":
        recs[1] = _session(1, last=120.0, done=True, cut=False)
        recs.append(_session(5, first=120.5, sent=120.1))
    elif fault == "errored":
        recs[2] = _session(2, last=130.0, cut=False, error="broke")
    elif fault == "no_token_in_the_window":
        recs[0] = _session(0, last=99.0)
    got = sessions.window_report(recs, 4, W0, W1, T0)
    assert (got["sessions_ready"], got["window_admissions"],
            got["failed"]) == (ready, admissions, failed)


def test_silence_is_the_gaps_over_100_ms_at_every_client_together():
    def seconds(arrivals):
        return sum(n for _, n in sessions.silences(arrivals, W0, W1))

    a = [100.0 + i / 20 for i in range(800)]            # every 50 ms
    assert sessions.silences(a, W0, W1) == []
    # one client pauses for 1 s while another goes on: no silence
    b = [t for t in a if not 110.0 < t < 111.0]
    assert sessions.silences(a + b, W0, W1) == []
    # all pause: the whole gap counts, and the window's edges close one
    assert sessions.silences(b, W0, W1) == [[pytest.approx(10.0),
                                             pytest.approx(1.0)]]
    assert seconds([t for t in a if t < 139.0]) == pytest.approx(1.05)
    assert seconds([]) == 40.0


def test_the_sample_is_of_cut_streams_split_at_the_windows_opening():
    recs = [_session(i, first=92.0 + i) for i in range(8)]
    recs[5]["prompt_len"] = 60          # the longest context at the opening
    recs[6] = _session(6, last=103.0)   # 6 tokens inside: not judged
    got = sessions.sample_sessions(recs, 7, W0, W1)
    assert len(got) == sessions.N_CHECK and 5 in [s["idx"] for s in got]
    assert 6 not in [s["idx"] for s in got]
    assert got == sessions.sample_sessions(recs, 7, W0, W1)
    assert {tuple(s["idx"] for s in sessions.sample_sessions(
        recs, seed, W0, W1)) for seed in range(8)} != {
        tuple(s["idx"] for s in got)}
    for s in got:
        rec = recs[s["idx"]]
        before = sum(1 for t in rec["token_times"] if t < W0)
        assert s["prefix"] == rec["tokens"][:before] and before > 0
        assert s["judged"] == rec["tokens"][before:before + sessions.N_TOKENS]
        assert rec["cut"] and not rec["done"]
    assert sessions.sample_sessions(recs[:3], 7, W0, W1) \
        == sorted(sessions.sample_sessions(recs[:3], 7, W0, W1),
                  key=lambda s: s["idx"])
    assert len(sessions.sample_sessions(recs[:3], 7, W0, W1)) == 3


def test_the_three_readers_on_records_made_by_hand():
    def read(name, rec):
        return manifest.layer_metric_reader(name)(rec)

    steps = [{"t": 1.0, "kind": "decode"}] * 5
    rec = {"kind": "serve", "window_s": 40.0,
           "sessions": {"ready_s": 3.25, "silence_s": 0.5},
           "program": {"steps": steps}}
    assert read("window_admissions", rec) == 0
    assert read("sessions_ready_s", rec) == 3.25
    assert read("stream_silence_share", rec) == pytest.approx(0.0125)
    rec["program"]["steps"] = steps + [{"t": 2.0, "kind": "prefill"},
                                       {"t": 3.0, "kind": "chunk"}]
    assert read("window_admissions", rec) == 2
    # an untraced run recorded nothing of the program: no count
    assert read("window_admissions", dict(rec, program=None)) is None
    # a `serve` cell's records, a train cell's, and none at all
    for other in ({"kind": "serve", "window_s": 40.0,
                   "program": {"steps": steps}},
                  {"kind": "train", "window_s": 40.0}, {}):
        for name in READERS:
            assert read(name, other) is None, (name, other)


# -- tiny rehearsals of the whole runner on the CPU --------------------------


def _cell(**mix):
    return {"name": "tiny_sessions.doc_sessions", "chips": 1,
            "config_file": copy.deepcopy(TINY),
            "traffic_file": dict(TINY_MIX, **mix)}


def _args(seed, trace=0, seconds=2.0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace,
                                 rate=None, t_start=time.monotonic(),
                                 workload=CELL)


def test_a_tiny_sessions_rehearsal_is_correct_and_its_line_is_the_contracts(
        tmp_path, jax_cache_config):
    args = _args(2 ** 31 + 23)
    res = sessions.run(_cell(), args, str(tmp_path), allow_cpu=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert (res["attempted"], res["failed"]) == (4, 0)
    assert checks["compared"]["window_admissions"] == [0, 0]
    assert checks["compared"]["sessions_ready"] == [True, True]
    assert checks["compared"]["compiles_in_window"] == [0, 0]
    assert 0 < checks["compared"]["sessions_ready_s"][0] < 1.5
    assert checks["compared"]["ref_max_logit_gap"][0] \
        <= checks["compared"]["ref_max_logit_gap"][1] == 0.5
    # `context_per_slot` reached the engine: the pool is four slots of it
    assert checks["memory"]["kv_pool_tokens"] == 4 * 4096
    assert checks["context_per_slot"] == 4096
    # the weights are the traffic's draw, the prompts the seed's
    job = json.load(open(tmp_path / "loadgen_job.json"))
    assert (job["seed"], job["traffic"]["weights_seed"]) == (2 ** 31 + 23, 7)
    # every stream is cut, the sample is of cut streams, and what is judged
    # was produced deep in a session, not after a prefill
    recs = [json.loads(x) for x in open(tmp_path / "requests.jsonl")]
    assert len(recs) == 4 and all(r["cut"] and not r["done"] for r in recs)
    assert len(checks["sampled"]) == 4 and checks["ref_tokens"] == 64
    by_idx = {r["idx"]: r for r in recs}
    assert all(c > by_idx[i]["prompt_len"] + 16
               for i, c in zip(checks["sampled"], checks["sampled_context"]))
    # the engine saw what the clients saw: the warm requests finished before
    # the window, nothing in it, every session resident at both edges
    assert checks["engine_requests_open"] == checks["engine_requests_close"]
    assert tuple(checks["load_open"]) == tuple(checks["load_close"]) == (0, 4)
    win = res["records"]["window"]
    assert win["tokens"] == round(
        res["end_to_end"]["serve_tokens_per_s"] * 2.0) > 4 * 16
    # the line of the contract, untraced
    bench = manifest.load_manifest()
    line = json.loads(json.dumps(bench_run.emit(bench, args, res)))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # an untraced run: the client-side readers read, the program's does not
    rec = res["records"]
    assert manifest.layer_metric_reader("sessions_ready_s")(rec) \
        == checks["compared"]["sessions_ready_s"][0]
    assert 0.0 <= manifest.layer_metric_reader(
        "stream_silence_share")(rec) < 1.0
    assert manifest.layer_metric_reader("window_admissions")(rec) is None
    for name in ("slot_occupancy", "stream_gap_p95_ms"):
        assert manifest.layer_metric_reader(name)(rec) is not None, name


def test_a_traced_rehearsal_counts_no_admission_in_the_programs_records(
        tmp_path, monkeypatch, jax_cache_config):
    from tests.benchmarks.test_benchmark_program_trace import _scopes

    monkeypatch.setattr(sessions, "TRACE_S", 0.3)
    monkeypatch.setattr(sessions.program_trace, "reduce_scopes",
                        lambda path: _scopes())
    args = _args(2 ** 31 + 29, trace=1)
    res = sessions.run(_cell(), args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["prefills_recorded_in_window"] == 0
    rec = res["records"]
    kinds = {s["kind"] for s in rec["program"]["steps"]}
    assert kinds == {"decode"}
    assert not [s for s in rec["program"]["spans"]
                if s[0] == "decode.prefill"]
    bench = manifest.load_manifest()
    line = json.loads(json.dumps(bench_run.emit(bench, args, res)))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    got = line["metrics"]
    assert got["window_admissions"] == {"value": 0.0, "unit": "count"}
    assert got["sessions_ready_s"]["value"] > 0
    assert "stream_silence_share" in got
    assert {"engine_step_p50_ms.tput", "engine_host_share.tput",
            "kv_block_used_share.tput", "slot_occupancy",
            "state_rows_used_share.sessions", "stream_gap_p95_ms"} \
        <= set(got)
    assert got["state_rows_used_share.sessions"]["value"] == 1.0
    assert got["slot_occupancy"]["value"] == 1.0


@pytest.mark.parametrize("fault", ["lead_too_short", "a_session_ends",
                                   "a_token_altered_where_it_is_produced",
                                   "a_token_flipped_before_the_window"])
def test_a_broken_run_is_not_correct(tmp_path, monkeypatch, fault,
                                     jax_cache_config):
    """The rest of a run driven with the timed path, or the window, broken
    underneath: each fault alone turns `correct` false, by the number that
    is there to catch it."""
    mix = {}
    if fault == "lead_too_short":
        mix = {"lead_s": 0.0}
    elif fault == "a_session_ends":
        mix = {"output_len": {"dist": "fixed", "value": 40},
               "context_per_slot": 128}
    elif fault == "a_token_altered_where_it_is_produced":
        from paddle_tpu.serving.decode import DecodeEngine

        emit = DecodeEngine._emit_token

        def altered(self, req, tok, phase):
            if len(req.generated) % 8 == 5:     # two of any 16 in a row
                tok = (int(tok) + 1) % 512
            return emit(self, req, tok, phase)

        monkeypatch.setattr(DecodeEngine, "_emit_token", altered)
    elif fault == "a_token_flipped_before_the_window":
        sample = sessions.sample_sessions

        def flipped(*a):
            got = sample(*a)
            got[0]["prefix"][-1] = (got[0]["prefix"][-1] + 1) % 512
            return got

        monkeypatch.setattr(sessions, "sample_sessions", flipped)
    res = sessions.run(_cell(**mix), _args(2 ** 31 + 31), str(tmp_path),
                       allow_cpu=True)
    compared = res["checks"]["compared"]
    assert not res["correct"], compared
    if fault == "lead_too_short":
        assert compared["sessions_ready"] == [False, True]
        assert compared["sessions_ready_s"][0] > 0.0
    elif fault == "a_session_ends":
        assert compared["window_admissions"][0] > 0
        assert res["failed"] > 0
    else:
        assert compared["sessions_ready"] == [True, True]
        assert compared["window_admissions"] == [0, 0]
        assert compared["ref_max_logit_gap"][0] \
            > compared["ref_max_logit_gap"][1]


# -- the control, at a size a test run can hold ------------------------------


def test_the_float8_control_is_not_correct_deep_in_a_sequence(published):
    """`sessions_control.readings` as the chip runs it, at the published
    widths with the blocks, experts and vocabulary cut for the CPU
    (`test_nemotron_cell.published`): the program decodes the LAST 16 tokens
    of one sequence greedily, 48 tokens deep, and they are judged as a
    session's in-window tokens are judged after its prompt and its
    pre-window tokens. The program's tokens are within the tolerance, the float8 reference's are not, and the
    rows are the yardstick's (`stream_gaps` reads the same statistic)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import nemotron_h as family
    from benchmarks.reference import nemotron_h_ref as ref
    from paddle_tpu.models import nemotron_h

    config, model, f32, ids, _ = published
    tol, n = config["logit_gap_tol"], sessions.N_TOKENS
    cfg = family.make_config(model)
    bf16, _ = family.init(cfg, SEED, dtype="bfloat16")
    forward = jax.jit(lambda p, i: nemotron_h.apply(p, cfg, i)[0])
    served = np.array(ids)          # the last 16 become the program's own
    for t in range(len(served) - n, len(served)):
        served[t] = int(forward(bf16, jnp.asarray(served)[None])[t - 1]
                        .argmax())
    served = [int(t) for t in served]
    got = sessions_control.readings(
        lambda: family.init(cfg, SEED)[0], model, [served], n, len(served))
    assert got["program"] <= tol / 2 < tol < got["control"], got
    assert got["bf16"] <= tol / 2, got
    want, _ = ref.stream_gaps(f32.top, f32.layer, model, [served[:-n]],
                              [served[-n:]], len(served))
    assert got["program"] == pytest.approx(want, abs=1e-5)
