"""The `setup_*` per-layer metrics (PR 37): what `benchmarks/harness/
boot_records.py` and the six readers make of the program's kept rows
(`compile.requests`, `boot.spans`), on a hand-made store; that every reader
leaves its metric out, and raises nothing, where the program has no such
rows (the driver runs the PARENT's program under this benchmark); and that
the six entries of BENCHMARK.json say what PERF.md says of them."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import boot_records, manifest  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402

NAMES = ("setup_first_program_s", "setup_compile_s", "setup_lower_s",
         "setup_cache_misses", "setup_engine_warm_s", "setup_train_build_s")
START = 1000.0      # the process's start on the store's clock
TRAIN_CELLS = ["bert_base.pretrain128", "bert_base.dp4"]
SERVE_CELLS = ["gpt2_large.chat_open", "gpt2_large.doc_closed",
               "olmoe_1b_7b.gen_closed", "joyai_llm_flash.rag_closed",
               "nemotron3_nano.reason_closed"]


def _row(t0, t1, cache="hit", trace_s=0.0, lower_s=0.0, fun="jit(f)"):
    return {"t0": START + t0, "t1": START + t1, "fun_name": fun,
            "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": t1 - t0 - trace_s - lower_s, "cache": cache,
            "retrieval_s": None, "saved_s": None, "tid": 1, "span": None}


def _span(name, t0, t1):
    return {"name": name, "t0": START + t0, "t1": START + t1, "sid": 1}


# backend start-up until 7.5 s, then requests: two that overlap (9.0-11.0
# and 10.0-12.0, a miss and one the cache does not keep), the warm-up's at
# 20-21 (no trace of its own: the join missed it), and one after the
# window (95-96)
ROWS = [_row(7.5, 8.0, trace_s=0.1, lower_s=0.2),
        _row(9.0, 11.0, cache="miss", trace_s=0.25, lower_s=0.5),
        _row(10.0, 12.0, cache="off"),
        dict(_row(20.0, 21.0, lower_s=0.75, fun="jit(_decode_fn)"),
             trace_s=None),
        _row(95.0, 96.0, trace_s=5.0)]
SPANS = [_span("boot.train_build", 8.0, 8.5),
         _span("boot.engine_build", 12.0, 14.0),
         _span("boot.engine_warmup", 14.0, 22.0),
         _span("boot.warm_phase", 14.0, 21.5),
         _span("boot.server_start", 22.0, 22.5),
         _span("boot.engine_build", 97.0, 99.0)]    # after the window


@pytest.fixture
def store(monkeypatch):
    """The program's store, hand-made: `tracing` answers from these."""
    kept = {"compile.requests": list(ROWS), "boot.spans": list(SPANS)}
    monkeypatch.setattr(tracing, "process_start", lambda: START)
    monkeypatch.setattr(tracing, "get_records",
                        lambda kind: list(kept.get(kind, ())))
    monkeypatch.setattr(tracing, "clock", lambda: START + 100.0)
    return kept


def _read(name, rec):
    return manifest.layer_metric_reader(name)(rec)


SERVE = {"kind": "serve", "window_s": 40.0,
         "program": {"window": (START + 30.0, START + 70.0), "spans": [],
                     "steps": [], "requests": []}}
# a train run's records carry no absolute time: its window is the first
# stretch of `window_s` without a compile request (21.0 .. 95.0 here)
TRAIN = {"kind": "train", "window_s": 42.0, "program": None}

WANT = {
    # 7.5 s to the first request; [7.5, 8] + [9, 12] + [20, 21] covered;
    # 0.3 + 0.75 + 0 + 0.75 traced and lowered; a miss and an uncached one
    "serve": {"setup_first_program_s": 7.5, "setup_compile_s": 4.5,
              "setup_lower_s": 1.8, "setup_cache_misses": 2,
              "setup_engine_warm_s": 10.0, "setup_train_build_s": None},
    "train": {"setup_first_program_s": 7.5, "setup_compile_s": 4.5,
              "setup_lower_s": 1.8, "setup_cache_misses": 2,
              "setup_engine_warm_s": None, "setup_train_build_s": 0.5},
}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("kind", ["serve", "train"])
def test_each_reader_on_a_hand_made_store(store, kind, name):
    got = _read(name, {"serve": SERVE, "train": TRAIN}[kind])
    want = WANT[kind][name]
    assert got == (None if want is None else pytest.approx(want))


def test_a_compile_inside_the_window_is_not_set_up(store):
    store["compile.requests"].insert(4, _row(55.0, 55.5, cache="miss",
                                             lower_s=0.25))
    assert _read("setup_cache_misses", SERVE) == 2
    assert _read("setup_lower_s", SERVE) == pytest.approx(1.8)
    # ... and breaks a train run's stretch: 21.0 to 55.0 and 55.5 to 95.0
    # are both shorter than the window, the stretch to the clock's "now"
    # (96 to 100) too, so the window cannot be placed and nothing is read
    assert _read("setup_compile_s", TRAIN) is None
    assert _read("setup_first_program_s", TRAIN) is None


def test_the_train_rule_takes_the_first_long_stretch(store):
    # a long stretch AFTER the last request counts (nothing compiled to
    # the end of the run): set-up is then every row
    store["compile.requests"][:] = ROWS[:4]
    rec = dict(TRAIN, window_s=70.0)
    assert _read("setup_compile_s", rec) == pytest.approx(4.5)
    rec = dict(TRAIN, window_s=80.0)    # longer than any stretch
    assert _read("setup_compile_s", rec) is None
    # set-up's own pauses are shorter than a window: 8 s (12.0 to 20.0)
    rec = dict(TRAIN, window_s=8.0)
    assert _read("setup_compile_s", rec) == pytest.approx(3.5)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("broken", [
    "empty", "no_process_start", "no_store", "untraced_serve",
    "window_missing", "window_empty", "window_no_number", "rows_lack_t1",
    "rows_lack_cache", "spans_lack_name"])
def test_readers_return_none_and_never_raise(store, monkeypatch, broken,
                                             name):
    """What the PARENT's program looks like to these readers: no rows, no
    `process_start`, no record lists at all. PR 35 was refused for a
    reader that raised there. And a LATER program's: the anchor there, the
    records or the rows in another layout."""
    rec = SERVE
    if broken == "empty":
        store["compile.requests"].clear()
        store["boot.spans"].clear()
    elif broken == "no_process_start":
        monkeypatch.delattr(tracing, "process_start")
    elif broken == "no_store":
        monkeypatch.delattr(tracing, "get_records")
    elif broken == "untraced_serve":
        rec = dict(SERVE, program=None)
    elif broken.startswith("window"):
        rec = dict(SERVE, program={
            "window_missing": {"spans": []}, "window_empty": {"window": ()},
            "window_no_number": {"window": ("soon", None)}}[broken])
    elif broken == "rows_lack_t1":
        store["compile.requests"][:] = [
            {k: v for k, v in r.items() if k != "t1"} for r in ROWS]
    elif broken == "rows_lack_cache":
        store["compile.requests"][:] = [
            {k: v for k, v in r.items() if k != "cache"} for r in ROWS]
    else:
        store["boot.spans"][:] = [{"t0": 1.0, "t1": 2.0}]
    assert _read(name, rec) is None
    if broken.startswith(("rows", "spans")):
        assert _read(name, TRAIN) is None
    assert _read(name, dict(TRAIN, window_s=None)) is None
    assert _read(name, {}) is None


def test_the_live_store_of_this_process_is_read():
    """No hand-made store: the readers on what this process recorded."""
    import jax.numpy as jnp

    jnp.ones((3,)) + 1      # at least one compile request, long before now
    now = tracing.clock()
    rec = {"kind": "serve", "program": {"window": (now, now + 1.0)}}
    first = _read("setup_first_program_s", rec)
    assert first is not None and 0.0 < first < now - tracing.process_start()
    assert _read("setup_compile_s", rec) > 0
    assert _read("setup_cache_misses", rec) >= 0


def test_the_six_entries_of_the_manifest():
    bench = manifest.load_manifest()
    cells = [w["name"] for w in bench["workloads"]]
    serve = [w["name"] for w in bench["workloads"]
             if manifest.find_cell(bench, w["name"])["traffic_file"]["kind"]
             == "serve"]
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"] in NAMES}
    assert sorted(entries) == sorted(NAMES)
    for name, m in entries.items():
        assert m["moves"] == "setup_s" and m["layer"] == "boot"
        assert m["better"] == "lower"
        assert set(m["workloads"]) <= set(cells)
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "layer_metrics", name + ".py"))
    # the seven cells this PR found (a later cell may join the lists)
    assert set(entries["setup_engine_warm_s"]["workloads"]) >= set(SERVE_CELLS)
    assert set(entries["setup_engine_warm_s"]["workloads"]) <= set(serve)
    assert set(entries["setup_train_build_s"]["workloads"]) >= set(
        TRAIN_CELLS)
    assert not set(entries["setup_train_build_s"]["workloads"]) & set(serve)
    for name in NAMES[:4]:
        assert set(entries[name]["workloads"]) >= set(
            SERVE_CELLS + TRAIN_CELLS)
    assert entries["setup_cache_misses"]["source"] == "program_counter"
    assert all(entries[n]["source"] == "program_span"
               for n in NAMES if n != "setup_cache_misses")
    # every cell reports the metric they move
    assert "workloads" not in next(m for m in bench["end_to_end"]
                                   if m["name"] == "setup_s")


# -- the real runners, tiny, on the CPU: the readers on a run's own records --


def _tiny_scopes():
    return {"devices_seen": 1, "busy_s": 1.0, "scoped_ops": 3,
            "by_scope": {"mlp": 1.0},
            "programs": {"jit__decode_fn": {"total_s": 1.0,
                                            "by_scope": {"mlp": 1.0}}}}


def test_the_serve_runner_s_own_records_give_their_five(tmp_path, monkeypatch):
    """A traced run of the serve runner: the window's opening is on the
    rows' clock, the engine's boot spans lie before it, and what the run
    calls `setup_s` is the window's opening less the process's start."""
    import time
    import types

    from benchmarks.kinds import serve
    from tests.benchmarks.test_benchmark_run import _serve_cell

    monkeypatch.setattr(serve, "TRACE_S", 0.3)
    monkeypatch.setattr(serve.program_trace, "reduce_scopes",
                        lambda path: _tiny_scopes())
    t_start = time.monotonic()
    args = types.SimpleNamespace(seed=2 ** 31 + 37, seconds=2.0, trace=1,
                                 rate=None, t_start=t_start)
    res = serve.run(_serve_cell(), args, str(tmp_path), allow_cpu=True)
    rec = res["records"]
    got = {name: _read(name, rec) for name in NAMES}
    assert got.pop("setup_train_build_s") is None
    assert all(v is not None for v in got.values()), got
    w0 = rec["program"]["window"][0]
    # this run's own boot lies between its start and its window
    boot = boot_records.load(rec)
    mine = [r for r in boot["setup"] if r["t0"] >= t_start]
    assert mine and all(r["t1"] <= w0 for r in mine)
    spans = [s for s in boot["spans"] if s["t0"] >= t_start]
    assert {"boot.engine_build", "boot.engine_warmup", "boot.warm_phase",
            "boot.server_start"} <= {s["name"] for s in spans}
    assert res["end_to_end"]["setup_s"] == pytest.approx(
        w0 - t_start, abs=0.05)
    # nothing compiled in the window, so no row begins inside it
    assert res["checks"]["compiles_in_window"] == 0
    assert not [r for r in tracing.get_records("compile.requests")
                if w0 <= r["t0"] < rec["program"]["window"][1]]
    assert got["setup_compile_s"] >= boot_records.union_seconds(mine)
    assert got["setup_lower_s"] > 0 and got["setup_engine_warm_s"] > 0


def test_the_train_runner_s_window_is_found_without_a_clock(tmp_path):
    """A train run's records carry no absolute time: the window is the
    first stretch of `window_s` without a compile request. At this toy
    size set-up pauses as long as the window, so the rule is held to what
    it can promise here: it finds a stretch, and no later than the real
    window's opening."""
    import time

    from benchmarks.kinds import train
    from tests.benchmarks.test_benchmark_run import TINY_BERT, _args

    cell = {"name": "tiny.train", "chips": 1, "config_file": TINY_BERT,
            "traffic_file": {
                "kind": "train", "mesh": {"dp": 1}, "seq_len": 32,
                "batch_per_chip": 8, "mask_rate": 0.15, "chunk_steps": 4}}
    t_start = time.monotonic()
    res = train.run(cell, _args(tmp_path), str(tmp_path), allow_cpu=True)
    t_end = time.monotonic()
    rec = res["records"]
    boot = boot_records.load(rec)
    assert boot is not None
    opening = max(r["t1"] for r in boot["setup"])
    assert opening <= t_end - rec["window_s"] + 0.05
    assert any(s["name"] == "boot.train_build" and s["t0"] >= t_start
               for s in tracing.get_records("boot.spans"))
    for name in NAMES[:4]:
        assert _read(name, rec) is not None
    assert _read("setup_engine_warm_s", rec) is None
    # (the toy's stretch may lie before its build: the span is then not
    # set-up's, and the metric is left out)
    build = _read("setup_train_build_s", rec)
    assert build is None or build > 0
