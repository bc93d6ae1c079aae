"""What the benchmark reads of the program's own tracing (PR 24): the layer
scope of an op, device time by scope on recorded and synthetic traces, idle
gaps given to the innermost span with their total preserved, every reader
of the program's recording on hand-made records, their manifest entries,
and a tiny CPU rehearsal of a traced serve run with the program's recording
on (counts and control flow only)."""

import json
import os
import time
import types

import pytest

from benchmarks.harness import manifest, program_trace as pt, trace_reduce
from benchmarks.kinds import serve

DATA = os.path.join(os.path.dirname(__file__), "data")


# -- a synthetic .xplane.pb: the wire format, written by hand --------------


def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num, val):
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    if isinstance(val, str):
        val = val.encode()
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _entry(key, msg):
    return _field(1, key) + _field(2, msg)


def _plane(name, lines, ops_meta=(), stat_names=("tf_op", "program_id")):
    """lines: {line name: [(metadata id, start_ps, duration_ps)]};
    ops_meta: [(id, event name, op_name or None, program id or None)]."""
    out = _field(2, name)
    for i, s in enumerate(stat_names, 1):
        out += _field(5, _entry(i, _field(1, i) + _field(2, s)))
    for mid, ev_name, op_name, pid in ops_meta:
        em = _field(1, mid) + _field(2, ev_name)
        if op_name is not None:
            em += _field(5, _field(1, 1) + _field(5, op_name))
        if pid is not None:
            em += _field(5, _field(1, 2) + _field(3, pid))
        out += _field(4, _entry(mid, em))
    for lname, events in lines.items():
        line = _field(2, lname) + _field(3, 1000)      # timestamp_ns
        for mid, start_ps, dur_ps in events:
            line += _field(4, _field(1, mid) + _field(2, start_ps)
                           + _field(3, dur_ps))
        out += _field(3, line)
    return _field(1, out)


US = 10 ** 6   # picoseconds


@pytest.fixture(scope="module")
def scoped_xplane(tmp_path_factory):
    """One device, one program `jit_step_fn(77)`: forward and backward ops
    under layer scopes, a scan slice, a while wrapper and an op with no
    metadata; on the host a turn with a dispatch and a resolve inside."""
    meta = [
        (1, "%fusion.1 = bf16[8,8]{1,0} fusion(%a)",
         "jit(step_fn)/jvp(layers)/attention/bnts,bsnh->btnh/dot_general",
         77),
        (2, "%fusion.2 = bf16[8,8]{1,0} fusion(%b)",
         "jit(step_fn)/transpose(jvp(layers))/attention/mul", 77),
        (3, "%fusion.3 = f32[8,8]{1,0} fusion(%c)",
         "jit(step_fn)/optimizer/add", 77),
        (4, "%copy.4 = bf16[1,8]{1,0} copy(%d)",
         "jit(step_fn)/layers/while/body/dynamic_slice", 77),
        (5, "%while.5 = (bf16[8]) while(%e)", "jit(step_fn)/layers/while",
         77),
        (6, "%copy.6 = bf16[36,8]{1,0} copy(%f)", None, 77),
        (7, "%fusion.7 = bf16[8]{0} fusion(%g)",
         "jit(step_fn)/transpose(jvp(mlm_head))/dot_general:", 77),
        (8, "%fusion.8 = bf16[8]{0} fusion(%h)",
         "jit(step_fn)/jvp(layers)/add", 77),
        (20, "jit_step_fn(77)", None, None),
    ]
    ops = [(5, 0, 60 * US),                      # wrapper: left out
           (1, 0, 10 * US), (2, 10 * US, 20 * US), (4, 30 * US, 5 * US),
           (8, 35 * US, 5 * US), (3, 40 * US, 10 * US),
           (6, 60 * US, 10 * US), (7, 90 * US, 10 * US)]
    host_meta = [(1, "decode.turn", None, None),
                 (2, "decode.dispatch", None, None),
                 (3, "engine_dispatch", None, None),
                 (4, "decode.resolve", None, None)]
    host = [(1, 45 * US, 43 * US), (3, 51 * US, 8 * US),
            (2, 52 * US, 6 * US), (4, 70 * US, 15 * US)]
    space = _plane("/device:TPU:0",
                   {"XLA Ops": ops, "XLA Modules": [(20, 0, 100 * US)]},
                   meta) + _plane("/host:CPU", {"python3": host}, host_meta)
    path = tmp_path_factory.mktemp("xplane") / "scoped.xplane.pb"
    path.write_bytes(space)
    return str(path)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jit(main)/layers/while/body/closed_call/qkv/dot_general",
     "qkv"),
    ("jit(step_fn)/transpose(jvp(layers))/attention/mul", "attention"),
    ("jit(step_fn)/transpose(jvp(mlm_head))/dot_general:", "mlm_head"),
    ("jit(step_fn)/jvp(layers)/proj/jit(dropout)/select_n", "proj"),
    ("jit(fn)/layers/while/body/dynamic_slice", "layers.carry"),
    ("jit(fn)/layers/while/body/dynamic_update_slice", "layers.carry"),
    ("jit(fn)/layers/while/body/closed_call/add", "layers.other"),
    ("jit(fn)/head/ln/div", "ln"),
    ("jit(step_fn)/optimizer/clip/mul", "clip"),
    ("jit(f)/dot_general:", "unscoped"), (None, "unscoped"), ("", "unscoped"),
    # a primitive or a jitted helper NAMED like a scope is not one
    ("jit(f)/jit(loss)/loss", "unscoped"),
])
def test_scope_of_an_op_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_scope_reduction_on_the_synthetic_plane(scoped_xplane):
    got = pt.reduce_scopes(scoped_xplane)
    us = 1e-6
    assert got["by_scope"] == pytest.approx({
        "attention": 30 * us, "layers.carry": 5 * us,
        "layers.other": 5 * us, "optimizer": 10 * us, "unscoped": 10 * us,
        "mlm_head": 10 * us})
    assert got["busy_s"] == pytest.approx(70 * us)
    # totals preserved: every op's time is in exactly one scope
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])
    assert list(got["programs"]) == ["jit_step_fn"]
    assert got["programs"]["jit_step_fn"]["total_s"] == pytest.approx(
        70 * us)
    assert got["scoped_ops"] == 6
    assert got["unscoped_top"] == [["copy__bf16_36_8", pytest.approx(
        10 * us)]]
    # the writer above is read the same by the profiler's own reader
    old = trace_reduce.reduce_loaded(
        trace_reduce.load_xplane(scoped_xplane, ("decode.turn",)), "other")
    assert old["busy_s"] == pytest.approx(got["busy_s"])


@pytest.mark.parametrize("name,devices", [("tiny_v5e", 1),
                                          ("tiny_v5e_4chips", 4)])
def test_scope_reduction_on_the_recorded_traces(name, devices):
    """Recorded before the program had scopes: all of it is `unscoped`,
    the total is the busy time the old reduction reads, and a reader of
    scopes finds nothing to read."""
    path = os.path.join(DATA, name + ".xplane.pb")
    got = pt.reduce_scopes(path)
    old = trace_reduce.reduce_loaded(trace_reduce.load_xplane(path, ()),
                                     "other")
    assert got["devices_seen"] == devices == old["devices_seen"]
    assert set(got["by_scope"]) == {"unscoped"} and not got["scoped_ops"]
    assert got["by_scope"]["unscoped"] == pytest.approx(got["busy_s"],
                                                        rel=0.02)
    assert got["busy_s"] == pytest.approx(old["busy_s"], rel=1e-3)
    assert list(got["programs"]) == ["jit_f"]
    assert pt.device_scopes({"trace": old, "scopes": got}) is None


def test_idle_gaps_go_to_the_innermost_span_totals_preserved(scoped_xplane):
    names = ("engine_dispatch",) + serve.LOOP_SPANS
    loaded = trace_reduce.load_xplane(scoped_xplane, names)
    gaps = trace_reduce.reduce_loaded(loaded, "engine_other",
                                      innermost=True)["idle_gaps"]
    assert len(gaps) <= 10      # the contract's cap on `breakdown` lists
    got = dict((n, s) for n, s in gaps if not n.endswith(".longest"))
    us = 1e-6
    # device idle: [50,60) and [70,90) us of the window [0,100)
    assert got == pytest.approx({
        "decode.turn": (1 + 1 + 3) * us,      # 50-51, 59-60, 85-88
        "engine_dispatch": (1 + 1) * us,      # 51-52, 58-59
        "decode.dispatch": 6 * us,            # 52-58
        "decode.resolve": 15 * us,            # 70-85
        "engine_other": 2 * us})              # 88-90: no span
    assert sum(got.values()) == pytest.approx(30 * us)
    # the other attribution gives an overlap to every span that has it
    old = trace_reduce.reduce_loaded(loaded, "engine_other")["idle_gaps"]
    assert sum(s for n, s in old if not n.endswith(".longest")) > 30 * us


# -- the readers, on hand-made records --------------------------------------


def _program():
    spans = [
        ("decode.turn", 10.0, 10.2, 1, {"sid": 1}),
        ("decode.prefill", 10.02, 10.12, 1, {"sid": 2, "rid": 7}),
        ("decode.prefill.wait", 10.03, 10.11, 1, {"sid": 3, "rid": 7}),
        ("decode.resolve.wait", 10.13, 10.19, 1, {"sid": 4}),
        ("decode.turn", 10.3, 10.4, 1, {"sid": 5}),
        ("decode.resolve.wait", 10.31, 10.39, 1, {"sid": 6}),
        ("http.first_write", 10.125, 10.127, 2, {"sid": 7, "rid": 7}),
    ]
    steps = [{"t": 10.0 + 0.1 * i, "kind": "decode", "slots": 4, "live": 2,
              "live_tokens": 60, "blocks_used": 5 + i, "blocks_usable": 20}
             for i in range(5)] + [
        {"t": 10.02, "kind": "prefill", "slots": 1, "live": 1,
         "live_tokens": 30, "blocks_used": 19, "blocks_usable": 20}]
    requests = [{"rid": 7, "arrival": 9.990, "t_submit": 9.993,
                 "enqueued_at": 9.993, "admitted_at": 10.02,
                 "t_first": 10.125, "t_finish": 10.9, "n_tokens": 8}]
    return {"spans": spans, "steps": steps, "requests": requests,
            "window": (10.0, 11.0), "dropped": 0}


@pytest.mark.parametrize("name,want", [
    ("engine_step_p50_ms", 100.0),
    ("engine_prefill_share", 0.1),
    ("engine_queue_wait_p50_ms", 27.0),
    ("engine_host_share", (0.2 - 0.08 - 0.06) + (0.1 - 0.08)),
    ("front_ttft_overhead_p50_ms", 3.0 + 2.0),
    ("kv_block_used_share", 7 / 20),
    # gaps 10.0-10.1 (holds the prefill that starts at 10.02) .. 10.3-10.4,
    # two sequences live in each: one of four holds an admission
    ("prefill_gap_share", 2 / 8),
])
def test_program_readers(name, want):
    read = manifest.layer_metric_reader(name)
    assert read({"kind": "serve", "program": _program()}) \
        == pytest.approx(want)
    assert manifest.layer_metric_reader(name + ".tput") is not None
    # nothing to read (the program's recording off, an older program, a
    # training cell): the metric is left out, nothing raises
    assert read({"kind": "serve"}) is None
    assert read({"kind": "serve", "program": None}) is None
    assert read({"kind": "train", "program": _program()}) is None


def _scopes():
    return {"devices_seen": 1, "busy_s": 4.0, "scoped_ops": 9,
            "by_scope": {"attention": 0.5, "optimizer": 0.9, "clip": 0.1,
                         "mlp": 2.0, "unscoped": 0.5},
            "programs": {
                "jit__prefill_fn": {"total_s": 1.0,
                                    "by_scope": {"mlp": 1.0}},
                "jit__decode_fn": {"total_s": 3.0, "by_scope": {
                    "mlp": 0.3, "ln": 0.1, "head": 0.05, "qkv": 0.15,
                    "layers.carry": 1.8, "kv_gather": 0.3,
                    "unscoped": 0.3}}}}


@pytest.mark.parametrize("name,kind,want", [
    ("decode_compute_share", "serve", 0.6 / 3.0),
    ("decode_compute_share.tput", "serve", 0.6 / 3.0),
    ("optimizer_share", "train", 1.0 / 4.0),
    ("attention_share", "train", 0.5 / 4.0),
])
def test_scope_readers(name, kind, want):
    read = manifest.layer_metric_reader(name)
    rec = {"kind": kind, "trace": {"devices_seen": 1}, "scopes": _scopes()}
    assert read(rec) == pytest.approx(want)
    other = "train" if kind == "serve" else "serve"
    assert read(dict(rec, kind=other)) is None
    assert read({"kind": kind, "trace": None}) is None          # untraced
    assert read(dict(rec, scopes={"devices_seen": 1, "scoped_ops": 0,
                                  "busy_s": 1.0, "by_scope": {},
                                  "programs": {}})) is None     # no scopes


PROGRAM_METRICS = {
    "engine_step_p50_ms": "program_span",
    "engine_prefill_share": "program_span",
    "engine_queue_wait_p50_ms": "program_span",
    "engine_host_share": "program_span",
    "front_ttft_overhead_p50_ms": "program_span",
    "prefill_gap_share": "program_span",
    "kv_block_used_share": "program_counter",
    "optimizer_share": "device_trace", "attention_share": "device_trace",
    "decode_compute_share": "device_trace"}


def test_the_entries_that_read_the_programs_recording():
    """The twelve entries that waited in `program_metrics.json` are in
    BENCHMARK.json (PR 26), the harness twins they replace are gone, and
    no reader leans on a private name of the engine."""
    bench = manifest.load_manifest()
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR,
                                           "program_metrics.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    stems = {n.split(".")[0] for n in by_name}
    assert stems >= set(PROGRAM_METRICS)
    assert not stems & {"decode_step_p50_ms", "prefill_share",
                        "queue_wait_p50_ms", "kv_used_share"}
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, m in by_name.items():
        want = PROGRAM_METRICS.get(name.split(".")[0])
        if want is None:
            continue
        assert m["source"] == want, name
        assert set(m["workloads"]) <= set(cells), name
        for w in m["workloads"]:   # what it moves, its cells report
            assert m["moves"] in [e["name"] for e in manifest.cell_metrics(
                bench, w, "end_to_end")]
    # the cells this benchmark began with report them (a later PR's cell
    # reports what its own mechanism records: no list is pinned here)
    def reported(w):
        return {m["name"].split(".")[0] for m in manifest.cell_metrics(
            bench, w, "per_layer")}

    for w in ("bert_base.pretrain128", "bert_base.dp4"):
        assert reported(w) >= {"optimizer_share", "attention_share"}, w
    for w in ("gpt2_large.chat_open", "gpt2_large.doc_closed"):
        assert reported(w) >= {
            "engine_step_p50_ms", "engine_prefill_share",
            "engine_host_share", "kv_block_used_share",
            "prefill_gap_share", "decode_compute_share"}, w
    for name in os.listdir(os.path.join(manifest.BENCH_DIR,
                                        "layer_metrics")):
        with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                               name)) as f:
            text = f.read()
        assert not any(p in text for p in ("_prefill_one", "_dispatch",
                                           "_resolve")), name


def test_tiny_recorded_serve_rehearsal(tmp_path, monkeypatch):
    """A traced run of the real serve runner on the CPU, with the scope
    reduction stood in for (the CPU's trace holds no device op): the
    program's spans and records arrive in `records`, the files are
    written, and every reader of them finds something to read."""
    from tests.benchmarks.test_benchmark_run import _serve_cell

    monkeypatch.setattr(serve, "TRACE_S", 0.3)
    monkeypatch.setattr(serve.program_trace, "reduce_scopes",
                        lambda path: _scopes())
    args = types.SimpleNamespace(seed=2 ** 31 + 11, seconds=2.0, trace=1,
                                 rate=None, t_start=time.monotonic())
    res = serve.run(_serve_cell(), args, str(tmp_path), allow_cpu=True)
    assert res["correct"], res["checks"]
    rec = res["records"]
    program = rec["program"]
    assert {s[0] for s in program["spans"]} >= set(serve.LOOP_SPANS) \
        | {"http.generate", "http.first_write", "decode.ttft"}
    assert res["checks"]["recording"]["spans_per_s"] > 0
    assert res["checks"]["recording"]["dropped_spans"] == 0
    for name in ("program_spans.jsonl", "engine_steps.jsonl",
                 "engine_requests.jsonl", "device_scopes.json"):
        assert (tmp_path / name).stat().st_size > 0, name
    assert not (tmp_path / "engine_spans.jsonl").exists()

    def read(name):
        return manifest.layer_metric_reader(name)(rec)

    # the step records and the dispatch spans are one loop's: one a step
    decode_steps = [s for s in program["steps"] if s["kind"] == "decode"]
    dispatches = [s for s in program["spans"] if s[0] == "decode.dispatch"]
    assert abs(len(dispatches) - len(decode_steps)) <= 1
    assert read("engine_step_p50_ms") > 0
    assert 0.0 < read("engine_prefill_share") < 1.0
    assert 0.0 < read("engine_host_share") < 1.0
    assert 0.0 < read("prefill_gap_share") < 1.0
    assert 0.0 < read("kv_block_used_share") <= 1.0
    assert read("front_ttft_overhead_p50_ms") > 0
    assert read("engine_queue_wait_p50_ms") >= 0
    assert read("decode_compute_share") == pytest.approx(0.6 / 3.0)
    # the traced sub-window's live tokens come from the step records
    assert rec["trace"]["live_tokens_mean"] > 0
    assert rec["trace"]["decode_min_bytes"] > 0
