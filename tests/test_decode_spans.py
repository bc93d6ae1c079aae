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
              "decode.dispatch.build", "decode.dispatch.call",
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


# -- the turn from inside (PR 56): the dispatch's parts, the device's queue
# seen from the host, CPU beside wall, an admission's company, hiccups ------

TURN_CHILDREN = {"decode.admit", "decode.grow", "decode.dispatch",
                 "decode.flush", "decode.resolve"}


@pytest.fixture(scope="module")
def lazy_run(gpt_model):
    """One recorded run of the lazy loop: (spans, status at its end)."""
    t.stop_recording()
    t.clear_spans()
    eng = _engine(gpt_model, "lazy")
    try:
        with t.recorded():
            handles = [eng.submit(p, max_new_tokens=NEW) for p in PROMPTS]
            for h in handles:
                h.result(timeout_s=120)
            status = eng.status()
    finally:
        eng.stop()
    return t.get_spans(), status


def test_the_dispatchs_parts_and_the_flush_nest_by_parent(lazy_run):
    spans, _ = lazy_run
    by_sid = {s.args["sid"]: s for s in spans}
    parts = [s for s in spans if s.name in ("decode.dispatch.build",
                                            "decode.dispatch.call")]
    dispatches = [s for s in spans if s.name == "decode.dispatch"]
    assert len(parts) == 2 * len(dispatches) > 0
    for d in dispatches:
        build, call = sorted((s for s in parts
                              if s.args["parent"] == d.args["sid"]),
                             key=lambda s: s.ts)
        assert (build.name, call.name) == ("decode.dispatch.build",
                                           "decode.dispatch.call")
        # the build ends where the call begins, both inside the dispatch
        assert d.ts <= build.ts and build.ts + build.dur <= call.ts + 1e-6 \
            and call.ts + call.dur <= d.ts + d.dur + 1e-6
    # a fetch's device array is let go of inside its resolve, under a name
    releases = [s for s in spans if s.name == "decode.resolve.release"]
    resolves = {s.args["sid"]: s for s in spans
                if s.name == "decode.resolve"}
    assert sorted(s.args["parent"] for s in releases) == sorted(resolves)
    for s in releases:
        r = resolves[s.args["parent"]]
        assert r.ts <= s.ts and s.ts + s.dur <= r.ts + r.dur + 1e-6
    flushes = [s for s in spans if s.name == "decode.flush"]
    assert flushes and all(
        by_sid[s.args["parent"]].name == "decode.turn" for s in flushes)
    # every token and every stream's end went through a flush
    assert sum(s.args["items"] for s in flushes) \
        == len(PROMPTS) * (NEW + 1)


def test_a_turns_children_cover_it_to_within_the_glue(lazy_run):
    spans, _ = lazy_run
    turns = [s for s in spans if s.name == "decode.turn"]
    wall = named = 0.0
    for turn in turns:
        kids = sorted((s for s in spans
                       if s.args.get("parent") == turn.args["sid"]),
                      key=lambda s: s.ts)
        assert {s.name for s in kids} <= TURN_CHILDREN, kids
        for a, b in zip(kids, kids[1:]):      # one after the other
            assert a.ts + a.dur <= b.ts + 1e-6
        wall += turn.dur
        named += sum(s.dur for s in kids)
    # what lies under no child is the glue: `_sweep_cancelled`,
    # `_slot_config` and the tracer's own open and close
    assert named <= wall and (wall - named) / wall < 0.25, (wall, named)


def test_a_turn_carries_its_cpu_seconds_and_an_admission_its_company(
        lazy_run):
    spans, _ = lazy_run
    turns = [s for s in spans if s.name == "decode.turn"]
    assert turns and all(
        0.0 <= s.args["cpu_s"] <= s.dur + 0.01 for s in turns)
    assert sum(s.args["cpu_s"] for s in turns) > 0
    fills = [s for s in spans if s.name == "decode.prefill"]
    assert len(fills) == len(PROMPTS)
    company = [s.args["same_bucket_waiting"] for s in fills]
    # one bucket, submitted together: whoever is admitted while another
    # still waits has company, the last one has none
    assert all(isinstance(n, int) and 0 <= n < len(PROMPTS)
               for n in company) and company[-1] == 0


def test_the_queues_probe_is_on_every_dispatch_and_counted(lazy_run):
    spans, status = lazy_run
    probed = [s for s in spans
              if s.name in ("decode.dispatch", "decode.prefill")]
    assert all(isinstance(s.args["queue_empty"], bool) for s in probed)
    starved = [s for s in probed if s.args["queue_empty"]]
    for s in probed:
        if s.args["queue_empty"]:
            assert 0 < s.args["starved_s"] <= s.dur + 1e-6, s
        else:
            assert "starved_s" not in s.args, s
    # an empty engine's first admission finds nothing in flight
    first = min((s for s in probed if s.name == "decode.prefill"),
                key=lambda s: s.ts)
    assert first.args["queue_empty"]
    assert status["pipeline"]["starved"] == len(starved)


def _own_turns(gpt_model):
    """A lazy engine whose turns the test takes on its own thread."""
    eng = _engine(gpt_model, "lazy")
    eng.start = lambda: None
    eng._outbox = []
    return eng


@pytest.mark.parametrize("in_flight", [False, True, "until_built"])
def test_starved_s_is_set_when_nothing_is_in_flight_and_absent_when_a_step_is(
        gpt_model, in_flight):
    import types

    eng = _own_turns(gpt_model)
    probe = eng._queue_empty
    asked = []

    def behind_a_step():
        # the newest entry in flight is not done: a step is running (with
        # "until_built" it ends while the dispatch builds its batch, and
        # the probe where the build ends finds the queue empty)
        asked.append(1)
        if in_flight == "until_built" and len(asked) > 1:
            return probe()
        eng._inflight.append(types.SimpleNamespace(
            tok_dev=types.SimpleNamespace(is_ready=lambda: False)))
        try:
            return probe()
        finally:
            eng._inflight.pop()

    try:
        eng.submit([1, 2, 3], max_new_tokens=NEW)
        eng._turn()             # admitted, first step dispatched
        eng._drain()            # nothing in flight, tokens on the host
        assert not eng._inflight
        if in_flight:
            eng._queue_empty = behind_a_step
        with t.recorded():
            before = eng.status()["pipeline"]["starved"]
            eng._dispatch(eng._slot_config())
            after = eng.status()["pipeline"]["starved"]
        eng._drain()
    finally:
        eng.stop()
    (d,) = [s for s in t.get_spans() if s.name == "decode.dispatch"]
    call = next(s for s in t.get_spans()
                if s.name == "decode.dispatch.call")
    if in_flight == "until_built":
        # from the second probe, where the build ends, to the call's return
        assert d.args["queue_empty"] is True and len(asked) == 2
        assert call.dur - 1e-4 <= d.args["starved_s"] <= call.dur + 1e-4
        assert after == before + 1
    elif in_flight:
        assert d.args["queue_empty"] is False and "starved_s" not in d.args
        assert after == before and len(asked) == 2
    else:
        assert d.args["queue_empty"] is True and d.args["ids"] == "host"
        # from the probe at the span's opening to the call's return
        assert call.ts + call.dur - d.ts - 1e-4 <= d.args["starved_s"] \
            <= call.ts + call.dur - d.ts + 1e-6
        assert after == before + 1


def test_recording_off_a_turn_reads_the_flag_and_nothing_else(
        gpt_model, monkeypatch):
    """With recording off every site of the loop is one read of
    `tracing.recording`: nothing else of `tracing` is touched on the
    loop's thread, the CPU clock is not read and no array is probed."""
    import threading
    import time
    import types

    from paddle_tpu.serving import decode as D

    seen, clocks, probes, turns = [], [], [], []

    class Watched(types.ModuleType):
        def __getattribute__(self, name):
            if threading.current_thread().name == "paddle-tpu-decode":
                seen.append(name)
            return getattr(t, name)

    monkeypatch.setattr(D, "_tracing", Watched("tracing"))
    real_cpu = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: clocks.append(1) or real_cpu())
    eng = _engine(gpt_model, "lazy")
    real_probe, real_turn = eng._queue_empty, eng._turn
    eng._queue_empty = lambda: probes.append(1) or real_probe()
    eng._turn = lambda: turns.append(1) or real_turn()
    try:
        streams = [eng.submit(p, max_new_tokens=NEW).result(timeout_s=120)
                   for p in PROMPTS]
    finally:
        eng.stop()
    assert [len(x) for x in streams] == [NEW] * len(PROMPTS)
    assert turns and set(seen) == {"recording"}, set(seen)
    # a turn's sites: the loop, admit, grow, dispatch, flush, one a
    # resolve; a request's: prefill (two), first token, finish
    assert len(seen) <= 7 * len(turns) + 4 * len(PROMPTS), \
        (len(seen), len(turns))
    assert clocks == [] and probes == []
    assert not [th for th in threading.enumerate()
                if th.name == "paddle-tpu-hiccups"]


def _hiccup_threads():
    import threading

    return [th for th in threading.enumerate()
            if th.name == "paddle-tpu-hiccups"]


def test_the_hiccup_thread_is_the_recordings_and_one():
    assert _hiccup_threads() == []
    t.start_recording()
    (first,) = _hiccup_threads()
    t.start_recording(clear=False)      # started again: still the one
    with t.recorded():
        assert _hiccup_threads() == [first]
    assert _hiccup_threads() == [first] and first.daemon
    t.stop_recording()
    assert _hiccup_threads() == [] and not first.is_alive()
    for _ in range(3):                  # restarted: one again, a new one
        t.start_recording()
        (again,) = _hiccup_threads()
        assert again is not first
        t.stop_recording()
        assert _hiccup_threads() == []


def test_a_late_wake_is_a_row_of_host_hiccups():
    import threading

    stop = threading.Event()
    now = [100.0]
    # the thread asks to sleep until its next wake; the made-up sleeps run
    # over by 1 ms, 0.3 s, 40 ms (under the limit), then the stop
    over = [0.001, 0.3, 0.04]
    meant = []

    def sleep(seconds):
        assert seconds == pytest.approx(t.HICCUP_TICK_S)
        meant.append(now[0] + seconds)
        if not over:
            stop.set()
            return
        now[0] += seconds + over.pop(0)

    t._watch_hiccups(stop, sleep=sleep, now=lambda: now[0])
    (row,) = t.get_records("host.hiccups")
    assert row["t"] == pytest.approx(meant[1]) \
        and row["late_s"] == pytest.approx(0.3)
    assert len(meant) == 4


def _wait_for(probe, timeout_s=10.0):
    import time

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        time.sleep(0.02)
    raise AssertionError("nothing recorded in time")
