"""A boot from the inside (ISSUE 37, PERF.md section 3): one row a compile
request in `tracing`'s kept list `compile.requests`, joined from what JAX
reports through `jax.monitoring`, boot spans on the same clock kept whether
or not a recording is on, `DecodeEngine.status()["boot"]` computed from
both, and `telemetry.record_compile`'s split taken from the request's row:
a program that JAX's persistent cache returned is not a compile."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import paddle_tpu  # noqa: F401 — package init registers the listeners
from paddle_tpu.core.executor import _JitDispatch
from paddle_tpu.models import gpt
from paddle_tpu.observability import events, telemetry, tracing
from paddle_tpu.serving import (DecodeConfig, DecodeEngine, Server,
                                ServingConfig)


def _rows(since=0.0):
    """The compile requests that closed at or after `since` on the clock
    (the list is bounded: a position in it means nothing in a long-lived
    test worker)."""
    return [r for r in tracing.get_records("compile.requests")
            if r["t1"] >= since]


def _boot_spans(since=0.0):
    return [s for s in tracing.get_records("boot.spans")
            if s["t0"] >= since]


@pytest.fixture
def jax_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's own, keeping
    every program however small (as the benchmark and `chip_smoke.py`
    place it). `tests/conftest.py` gives the settings back."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _fresh_fn(scale):
    """A function no other test has compiled (the constant is its own)."""
    def boot_probe(x):
        return jnp.tanh(x) @ x * scale

    return boot_probe


def test_process_start_is_before_the_import_and_fixed():
    start = tracing.process_start()
    assert start <= tracing._IMPORT_STAMP <= tracing.clock()
    # within the life of a test worker, not of the machine
    assert tracing.clock() - start < 6 * 3600
    assert tracing.process_start() == start


def test_a_fresh_jit_is_one_row_a_miss_then_a_hit(jax_cache):
    fn, x = _fresh_fn(1.2345), jnp.ones((8, 8), jnp.float32)
    # both from ONE line: where the cache keys on metadata (an engine of
    # this worker asked for that), the caller's stack is part of the key
    for want in ("miss", "hit"):
        jax.clear_caches()  # the in-memory executables; the directory stays
        t_before = tracing.clock()
        jax.jit(fn)(x)
        (row,) = [r for r in _rows(t_before)
                  if r["fun_name"] == "jit(boot_probe)"]
        assert row["cache"] == want and row["backend_s"] > 0
        # tracing and lowering are paid on a hit too: no cache saves them
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        # on the engine's clock, and the parts lie inside the row
        assert t_before <= row["t0"] < row["t1"] <= tracing.clock()
        assert row["t1"] - row["t0"] >= (row["trace_s"] + row["lower_s"]
                                         + row["backend_s"]) - 1e-3
        assert tracing.last_compile_request(t_before) is _rows(t_before)[-1]
        if want == "miss":
            assert row["retrieval_s"] is None and row["saved_s"] is None
        else:
            assert row["retrieval_s"] > 0 and row["saved_s"] is not None
            assert row["backend_s"] >= row["retrieval_s"]


def test_lower_compile_is_one_row_not_two(jax_cache):
    fn, x = _fresh_fn(2.3456), jnp.ones((8, 8), jnp.float32)
    t_before = tracing.clock()
    jax.jit(fn).lower(x).compile()
    mine = [r for r in _rows(t_before) if r["fun_name"] == "jit(boot_probe)"]
    assert len(mine) == 1 and mine[0]["cache"] == "miss"


def test_without_a_cache_directory_the_row_says_off():
    t_before = tracing.clock()
    jax.jit(_fresh_fn(3.4567))(jnp.ones((8, 8), jnp.float32))
    mine = [r for r in _rows(t_before) if r["fun_name"] == "jit(boot_probe)"]
    assert len(mine) == 1 and mine[0]["cache"] == "off"


def test_rows_survive_a_recording_that_clears_and_are_spans_in_it():
    jax.jit(_fresh_fn(4.5678))(jnp.ones((4, 4), jnp.float32))
    before = _rows()
    assert before
    tracing.start_recording(clear=True)
    try:
        assert _rows() == before
        t_before = tracing.clock()
        outer = tracing.open_span("test.outer", "test")
        jax.jit(_fresh_fn(5.6789))(jnp.ones((4, 4), jnp.float32))
        outer.close()
        spans = [s for s in tracing.get_spans() if s.name == "xla.compile"
                 and s.args["fun_name"] == "jit(boot_probe)"]
    finally:
        tracing.stop_recording()
        tracing.clear_spans()
    assert len(spans) == 1
    (row,) = [r for r in _rows(t_before)
              if r["fun_name"] == "jit(boot_probe)"]
    # the same row, beside the span it interrupted
    assert spans[0].ts == row["t0"] and row["span"] == "test.outer"
    assert spans[0].args["span"] == "test.outer"
    # clear_spans() emptied the ring and left the process's rows alone
    assert tracing.get_spans() == [] and row in _rows()


def test_the_kept_lists_are_bounded():
    with tracing.boot_span("boot.test_filler"):
        pass
    assert tracing.KEPT_RECORDS["compile.requests"] == 4096
    for kind, bound in tracing.KEPT_RECORDS.items():
        assert tracing._records[kind].maxlen == bound


# -- the join itself, fed by hand ------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def test_trace_s_is_the_outermost_trace_never_the_sum():
    t_before = tracing.clock()
    tracing.compile_duration(TRACE, 0.1, fun_name="matmul")
    tracing.compile_duration(TRACE, 0.2, fun_name="tanh")
    tracing.compile_duration(TRACE, 0.5, fun_name="hand_fed")
    # a lowering traces inner functions again before it reports itself
    tracing.compile_duration(TRACE, 0.3, fun_name="add")
    tracing.compile_duration(LOWER, 0.0, fun_name="jit(hand_fed)")
    tracing.compile_event("/jax/compilation_cache/compile_requests_use_cache")
    tracing.compile_event("/jax/compilation_cache/cache_hits")
    tracing.compile_duration(
        "/jax/compilation_cache/compile_time_saved_sec", 3.0)
    tracing.compile_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    tracing.compile_duration(BACKEND, 0.0, fun_name="jit(hand_fed)")
    (row,) = [r for r in _rows(t_before) if r["fun_name"] == "jit(hand_fed)"]
    assert row["trace_s"] == 0.5 and row["cache"] == "hit"
    assert row["retrieval_s"] == 0.25 and row["saved_s"] == 3.0
    assert row["t1"] - row["t0"] >= 0.5


@pytest.mark.parametrize("events_fed, rows, lower_s", [
    # tracing and lowering whose request never closes: no row
    ([(TRACE, "a"), (LOWER, "jit(a)")], 0, None),
    # ... and the next request does not inherit them
    ([(TRACE, "a"), (LOWER, "jit(a)"), (BACKEND, "jit(b)")], 1, 0.0),
    # a trace of another function (an `eval_shape` long before) is not
    # this request's
    ([(TRACE, "z"), (LOWER, "jit(a)"), (BACKEND, "jit(a)")], 1, 0.125),
    # ... and one that ends after the lowering began is not either
    ([(LOWER, "jit(a)"), (TRACE, "hand_a"), (BACKEND, "jit(a)")], 1, 0.125),
    # a second compile() of one Lowered: a request with no lowering
    ([(LOWER, "jit(a)"), (BACKEND, "jit(a)"), (BACKEND, "jit(a)")], 2, 0.0),
])
def test_events_that_do_not_close_leave_no_row(events_fed, rows, lower_s):
    t_before = tracing.clock()
    ours = {"jit(a)": "jit(hand_a)", "jit(b)": "jit(hand_b)"}
    for event, fun in events_fed:
        tracing.compile_duration(event, 0.125, fun_name=ours.get(fun, fun))
    got = [r for r in _rows(t_before) if r["fun_name"] in ours.values()]
    tracing._compile_state().clear()
    assert len(got) == rows
    if rows:
        # no trace of its own: unknown, which is not 0
        assert got[-1]["lower_s"] == lower_s and got[-1]["trace_s"] is None


# -- boot spans and the operator's view ------------------------------------


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"
    params, _ = gpt.init(jax.random.key(0), cfg)
    return params, cfg


def _engine(model, **kw):
    params, cfg = model
    base = dict(block_size=8, num_blocks=64, decode_slots=(4,),
                prefill_buckets=(8, 16), precision="f32", max_len=64)
    base.update(kw)
    return DecodeEngine(params, cfg, DecodeConfig(**base))


def test_a_boot_span_is_one_row_and_a_span_only_in_a_recording():
    tracing.clear_spans()
    t_before = tracing.clock()
    with pytest.raises(ZeroDivisionError):
        with tracing.boot_span("boot.test_one_store") as facts:
            assert tracing.current_span().name == "boot.test_one_store"
            facts["n"] = 3
            1 / 0
    (row,) = [s for s in _boot_spans(t_before)
              if s["name"] == "boot.test_one_store"]
    assert row["n"] == 3 and t_before <= row["t0"] <= row["t1"]
    assert tracing.current_span() is None
    # recording off: the kept list is the one store, the ring gets nothing
    assert tracing.get_spans() == []
    with tracing.recorded():
        with tracing.boot_span("boot.test_one_store"):
            pass
        assert [s.name for s in tracing.get_spans()] \
            == ["boot.test_one_store"]
    tracing.clear_spans()
    assert len([s for s in _boot_spans(t_before)
                if s["name"] == "boot.test_one_store"]) == 2


def test_engine_boot_leaves_its_spans_with_recording_off(model):
    assert not tracing.recording
    t_boot = tracing.clock()
    eng = _engine(model)
    try:
        assert eng.warmup() == 4
        spans = _boot_spans(t_boot)
        names = [s["name"] for s in spans]
        assert names.count("boot.engine_build") == 1
        assert names.count("boot.engine_warmup") == 1
        build = spans[names.index("boot.engine_build")]
        assert build["weight_bytes"] == sum(
            int(v.nbytes) for v in eng.params.values())
        assert build["pool_bytes"] == sum(int(p.nbytes)
                                          for p in eng._pools)
        assert build["state_bytes"] == 0
        warm = spans[names.index("boot.engine_warmup")]
        assert warm["phases"] == 4
        phases = [s for s in spans if s["name"] == "boot.warm_phase"]
        assert [s["phase"] for s in phases] == [
            "prefill@8", "prefill@16", "decode@4", "assemble@4x4"]
        assert all(s["installed"] == "compiled" for s in phases)
        assert all(warm["t0"] <= s["t0"] <= s["t1"] <= warm["t1"]
                   for s in phases)
        # the compile requests of the grid say which span they fell in
        grid = [r for r in _rows(warm["t0"])
                if r["span"] == "boot.warm_phase"]
        assert {"jit(_prefill_fn)", "jit(_decode_fn)",
                "jit(_assemble_ids)"} <= {r["fun_name"] for r in grid}

        # a served window after warm-up asks for no executable
        t_served = tracing.clock()
        toks = eng.submit([1, 2, 3], max_new_tokens=6).result(
            timeout_s=120)
        toks2 = eng.submit(list(range(1, 12)), max_new_tokens=6).result(
            timeout_s=120)
        assert len(toks) == 6 and len(toks2) == 6
        assert _rows(t_served) == []

        # a second warm-up finds every phase in place
        assert eng.warmup() == 4
        again = [s for s in _boot_spans(t_served)
                 if s["name"] == "boot.warm_phase"]
        assert [s["installed"] for s in again] == ["compiled"] * 4
        assert _rows(t_served) == []
    finally:
        eng.stop()


def test_status_boot_adds_up_to_its_parts(model):
    eng = _engine(model, prefill_buckets=(8,))
    try:
        eng.warmup()
        boot = eng.status()["boot"]
    finally:
        eng.stop()
    rows = _rows()
    spans = tracing.get_records("boot.spans")
    assert boot["process_start"] == tracing.process_start()
    assert boot["ready_s"] == pytest.approx(max(
        s["t1"] for s in spans if s["name"] == "boot.engine_warmup")
        - tracing.process_start())
    assert 0 < boot["imported_s"] == tracing._IMPORT_STAMP \
        - tracing.process_start()
    assert boot["first_program_s"] == pytest.approx(
        min(r["t0"] for r in rows) - tracing.process_start())
    comp = boot["compile"]
    assert comp["requests"] == len(rows)
    assert comp["hits"] + comp["misses"] + comp["uncached"] \
        == comp["requests"]
    for part in ("lower_s", "backend_s"):
        assert comp[part] == pytest.approx(sum(r[part] for r in rows))
    # the clock the requests cover: at most their lengths' sum, at least
    # the parts that cannot overlap on one thread
    assert comp["covered_s"] <= sum(r["t1"] - r["t0"] for r in rows) + 1e-9
    assert len(comp["slowest"]) == 5
    assert comp["slowest"][0]["seconds"] == pytest.approx(
        max(r["t1"] - r["t0"] for r in rows))
    by_name = boot["spans_s"]
    assert by_name["boot.warm_phase"] <= by_name["boot.engine_warmup"]
    assert by_name["boot.engine_build"] > 0
    assert boot["ready_s"] >= by_name["boot.engine_warmup"]
    # by phase, with where each executable came from: the warm-up's spans
    phases = [s for s in spans if s["name"] == "boot.warm_phase"]
    assert boot["phases"][-3:] == [
        {"phase": s["phase"], "installed": s["installed"],
         "seconds": s["t1"] - s["t0"]} for s in phases[-3:]]
    assert [p["phase"] for p in boot["phases"][-3:]] == [
        "prefill@8", "decode@4", "assemble@4x4"]
    assert sum(p["seconds"] for p in boot["phases"]) == pytest.approx(
        by_name["boot.warm_phase"])
    # requests whose tracing was paid elsewhere are counted, not read as 0
    assert comp["untraced"] == sum(r["trace_s"] is None for r in rows)
    assert comp["trace_s"] == pytest.approx(
        sum(r["trace_s"] or 0.0 for r in rows))


def test_a_polled_status_makes_its_boot_view_only_when_a_row_was_added(
        monkeypatch):
    from paddle_tpu.serving import decode

    jax.jit(_fresh_fn(7.8912))(jnp.ones((4, 4), jnp.float32))
    first = decode._boot_status()
    made = []
    real = tracing.boot_summary
    monkeypatch.setattr(tracing, "boot_summary",
                        lambda: made.append(1) or real())
    # the same answer, and the caller's own copy of it
    again = decode._boot_status()
    again["ready_s"] = -1.0
    assert decode._boot_status() == first and made == []
    jax.jit(_fresh_fn(8.9123))(jnp.ones((4, 4), jnp.float32))
    with tracing.boot_span("boot.test_memo"):
        pass
    after = decode._boot_status()
    assert made == [1] and after != first
    assert "boot.test_memo" in after["spans_s"]
    assert decode._boot_status() == after and made == [1]


def test_server_start_and_train_build_are_boot_spans(model):
    from paddle_tpu.parallel import MeshConfig, make_mesh
    from paddle_tpu.parallel.train import make_train_step

    t_boot = tracing.clock()
    eng = _engine(model, prefill_buckets=(8,))
    server = Server(ServingConfig(), decode=eng)
    try:
        port = server.start(0)
        assert server.start(0) == port      # the second call: no span
    finally:
        server.stop()
        eng.stop()
    mesh = make_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])
    init_state, _ = make_train_step(
        lambda p, b, r: jnp.mean((b["x"] @ p["w"]) ** 2), optax.sgd(0.1),
        mesh, {"w": ("io", "model")})
    t_train = tracing.clock()
    state = init_state({"w": np.ones((4, 2), np.float32)})
    assert state.params["w"].shape == (4, 2)
    spans = _boot_spans(t_boot)
    names = [s["name"] for s in spans]
    assert names.count("boot.server_start") == 1
    assert names.count("boot.train_build") == 1
    # the server warms its engine inside its own start
    start = spans[names.index("boot.server_start")]
    warm = spans[names.index("boot.engine_warmup")]
    assert start["t0"] <= warm["t0"] and warm["t1"] <= start["t1"]
    # the optimizer's init program compiled inside the train build
    assert any(r["span"] == "boot.train_build" for r in _rows(t_train))


# -- telemetry: what XLA compiled, and what a cache returned ---------------


def _compiles(kind):
    return telemetry.COMPILES.value(kind=kind)


def test_a_jax_cache_hit_is_not_a_compile(jax_cache):
    kind = "boot_records_test"
    fn, x = _fresh_fn(6.789), jnp.ones((8, 8), jnp.float32)
    seq0 = events.recent()[-1]["seq"] if events.recent() else -1

    # both boots from ONE line: once an engine of this worker has made the
    # cache key on metadata (`compile_cache.key_on_metadata`), the stack of
    # the call that lowered is part of the key
    cold, warm = None, None
    for want, compiles, hits in (("compiled", 1, 0), ("jax_cache", 1, 1)):
        jax.clear_caches()      # the in-memory executables only
        cold, warm = warm, _JitDispatch(jax.jit(fn), kind)
        assert warm.warm(x) and warm.installed == want
        assert _compiles(kind) == compiles      # a hit did not raise it
        assert telemetry.COMPILE_CACHE.value(kind=kind,
                                             event="hit") == hits
    np.testing.assert_allclose(np.asarray(warm(x)), np.asarray(cold(x)))
    # a signature it compiled before comes back without a request
    t_before = tracing.clock()
    other = jnp.ones((4, 4), jnp.float32)
    assert warm.warm(other) and warm.warm(x)
    assert warm.installed == "remembered"
    assert [r["fun_name"] for r in _rows(t_before)].count(
        "jit(boot_probe)") == 1

    evs = [e for e in events.recent(kind="compile")
           if e["seq"] > seq0 and e["compile_kind"] == kind]
    assert [e["cache"] for e in evs][:2] == ["miss", "hit"]
    for e in evs:
        # the split of one `seconds`: tracing and lowering no cache saves
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
        assert e["seconds"] >= e["lower_s"] + e["backend_s"] - 1e-3
    hit = [e for e in events.recent(kind="compile_cache")
           if e["seq"] > seq0 and e["compile_kind"] == kind]
    assert hit and hit[0]["event"] == "hit" and hit[0]["seconds"] > 0


def test_compile_seconds_are_the_backends_when_the_row_is_known():
    kind = "boot_records_split"
    request = {"cache": "off", "trace_s": 0.5, "lower_s": 1.5,
               "backend_s": 2.0, "retrieval_s": None}
    telemetry.record_compile(kind, 4.25, request=request)
    telemetry.record_compile(kind, 7.0)     # no row: the whole, as before
    assert _compiles(kind) == 2
    series = [s for s in paddle_tpu.observability.snapshot()[
        "paddle_tpu_compile_seconds"]["series"]
        if s["labels"].get("kind") == kind]
    assert series[0]["count"] == 2 and series[0]["sum"] == 9.0
    ev = [e for e in events.recent(kind="compile")
          if e["compile_kind"] == kind]
    assert ev[0]["seconds"] == 4.25 and ev[0]["backend_s"] == 2.0
    assert "backend_s" not in ev[1]
