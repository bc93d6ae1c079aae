"""The decode engine's own spans and records (PERF.md section 3): both
loops emit the same names, every span names its cause and its request, the
step and request records account for every token, and with recording off a
span site is one branch: nothing is called, nothing is kept."""

import json
import urllib.request

import jax
import pytest

from paddle_tpu.observability import tracing as t
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

LOOP_SPANS = {"decode.turn", "decode.admit", "decode.prefill",
              "decode.prefill.wait", "decode.grow", "decode.dispatch",
              "decode.resolve", "decode.resolve.wait"}
REQUEST_SPANS = {"decode.queue_wait", "decode.ttft", "decode.decode",
                 "decode.generate"}
PROMPTS = ([1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11, 12], [13, 14])
NEW = 5


@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"
    params, _ = gpt.init(jax.random.key(0), cfg)
    return params, cfg


def _engine(gpt_model, loop):
    extra = {"prefill_chunk": 4} if loop == "sync" \
        else {"prefill_buckets": (16,)}
    return DecodeEngine(*gpt_model, DecodeConfig(
        block_size=8, num_blocks=64, decode_slots=(4,), precision="f32",
        max_len=64, **extra))


@pytest.fixture(autouse=True)
def _clean():
    t.stop_recording()
    t.clear_spans()
    yield
    t.stop_recording()
    t.clear_spans()


@pytest.mark.parametrize("loop", ["lazy", "sync"])
def test_both_loops_emit_the_same_spans_and_account_for_every_token(
        gpt_model, loop):
    eng = _engine(gpt_model, loop)
    try:
        with t.recorded():
            handles = [eng.submit(p, max_new_tokens=NEW) for p in PROMPTS]
            streams = [h.result(timeout_s=120) for h in handles]
            status = eng.status()
    finally:
        eng.stop()      # the loop's last turn closes its spans first
    spans = t.get_spans()
    steps = t.get_records("decode.steps")
    requests = t.get_records("decode.requests")
    names = {s.name for s in spans}
    assert LOOP_SPANS | REQUEST_SPANS <= names, \
        (LOOP_SPANS | REQUEST_SPANS) - names
    by_sid = {s.args["sid"]: s for s in spans}
    assert len(by_sid) == len(spans)
    turns = [s for s in spans if s.name == "decode.turn"]
    assert {s.args["loop"] for s in turns} == {loop}
    for s in spans:
        if s.name in LOOP_SPANS - {"decode.turn"}:
            # caused by a span of the same thread that encloses it
            cause = by_sid[s.args["parent"]]
            assert cause.tid == s.tid and cause.ts <= s.ts \
                and s.ts + s.dur <= cause.ts + cause.dur + 1e-6, s
        if s.name in REQUEST_SPANS | {"decode.prefill",
                                      "decode.prefill.wait"}:
            assert s.args["rid"] in {h.rid for h in handles}, s
    # each request has one of each request-level span
    for name in REQUEST_SPANS:
        assert sorted(s.args["rid"] for s in spans if s.name == name) \
            == sorted(h.rid for h in handles), name
    # the records: every finished request, every token
    assert sorted(r["rid"] for r in requests) \
        == sorted(h.rid for h in handles)
    for r, h, toks, prompt in zip(sorted(requests, key=lambda r: r["rid"]),
                                  handles, streams, PROMPTS):
        assert r["n_tokens"] == len(toks) == NEW
        assert r["prompt_len"] == len(prompt) and r["outcome"] == "length"
        assert r["arrival"] <= r["enqueued_at"] <= r["admitted_at"] \
            <= r["t_first"] <= r["t_finish"]
        assert r["preemptions"] == 0
    decode_steps = [s for s in steps if s["kind"] == "decode"]
    resolves = [s for s in spans if s.name == "decode.resolve"]
    n_first = len(PROMPTS)              # a request's first token is its
    assert sum(s.args["tokens"] for s in resolves) \
        == sum(len(x) for x in streams) - n_first    # prefill's
    assert sum(s["live"] for s in decode_steps) \
        >= sum(s.args["tokens"] for s in resolves)
    assert {s["kind"] for s in steps} == (
        {"decode", "chunk"} if loop == "sync" else {"decode", "prefill"})
    for s in steps:
        assert 0 < s["blocks_used"] <= s["blocks_usable"] == 63
        assert s["live"] <= s["slots"]
    assert status["step_ms"]["n"] >= 1 and status["step_ms"]["p50"] > 0


def test_recording_off_keeps_nothing_and_calls_nothing(gpt_model,
                                                       monkeypatch):
    calls = []
    for name in ("open_span", "record", "add_record", "record_span"):
        def site(*a, _n=name, _real=getattr(t, name), **k):
            calls.append((_n,) + tuple(x for x in a[:2]
                                       if isinstance(x, str)))
            return _real(*a, **k)
        monkeypatch.setattr(t, name, site)

    def boot_only():
        # a boot is not the hot path: its spans and the compile requests'
        # rows are kept with recording off (tests/test_boot_records.py)
        rest = [c for c in calls
                if not (c[0] == "open_span" and c[2] == "boot")
                and c[:2] not in (("add_record", "boot.spans"),
                                  ("add_record", "compile.requests"))]
        seen, calls[:] = list(calls), []
        assert rest == [], rest
        return seen

    eng = _engine(gpt_model, "lazy")
    try:
        assert ("open_span", "boot.engine_build", "boot") in boot_only()
        # the first request, unwarmed: its programs' compile requests are
        # rows, and no site of the loop calls into the store
        toks = eng.submit([1, 2, 3], max_new_tokens=NEW).result(
            timeout_s=120)
        assert ("add_record", "compile.requests") in boot_only()
        eng.warmup()
        assert ("open_span", "boot.warm_phase", "boot") in boot_only()
        # served warm: a span site with recording off is
        # `if tracing.recording`: no call into the store, so no clock
        # read, no allocation, no lock
        toks2 = eng.submit([4, 5, 6, 7], max_new_tokens=NEW).result(
            timeout_s=120)
    finally:
        eng.stop()
    assert len(toks) == NEW and len(toks2) == NEW
    assert calls == []
    assert t.get_spans() == [] and t.get_records("decode.steps") == [] \
        and t.get_records("decode.requests") == []


def test_http_request_is_the_parent_and_the_sampled_tree_keeps_its_names(
        gpt_model, tmp_path, monkeypatch):
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.httpd import Server

    monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1.0")
    eng = _engine(gpt_model, "lazy")
    srv = Server(ServingConfig(None, warmup=False), decode=eng)
    try:
        port = srv.start(0)
        with t.recorded():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps({"ids": [1, 2, 3],
                                 "max_new_tokens": NEW}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                tid = r.headers["X-Request-Id"]
                lines = [json.loads(x) for x in r.read().splitlines()]
            assert lines[-1]["done"] and lines[-1]["tokens"] == NEW
            spans = _wait_for(lambda: [
                s for s in t.get_spans() if s.name == "http.generate"])
            spans = t.get_spans()
    finally:
        srv.stop()
        eng.stop()
    root = [s for s in spans if s.name == "http.generate"][0]
    assert root.args["trace_id"] == tid
    mine = [s for s in spans if s.args.get("rid") == root.args["rid"]
            and s.name != "http.generate"]
    assert {"http.first_write", "decode.prefill", "decode.ttft",
            "decode.generate"} <= {s.name for s in mine}
    for s in mine:
        if s.name != "decode.prefill.wait":     # caused by its prefill
            assert s.args["parent"] == root.args["sid"], s
    (record,) = t.get_records("decode.requests") or [None]
    # sampled: the same spans, under the old names, in the distributed
    # trace that `tools/obsdump.py trace` reassembles
    t.flush_trace_sink()
    tree = t.build_trace_tree(t.read_trace_dir(str(tmp_path)), tid)
    assert [n["name"] for n in tree] == ["http.generate"]
    assert {"decode.queue_wait", "decode.prefill", "decode.ttft",
            "decode.decode", "decode.generate"} \
        <= {c["name"] for c in tree[0]["children"]}


def _wait_for(probe, timeout_s=10.0):
    import time

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        time.sleep(0.02)
    raise AssertionError("nothing recorded in time")
