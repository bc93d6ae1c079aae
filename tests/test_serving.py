"""Serving subsystem tests (ISSUE 3): bucket policy pad/slice, batcher
coalescing/timeout/backpressure/drain on a fake engine (no jax), the
Predictor's opt-in bucketing, and the live end-to-end acceptance —
concurrent mixed-batch-size HTTP traffic against a running Server with
the compile-event assertion (total XLA compiles ≤ configured buckets)
plus the full-queue 503 scenario, over real sockets.

Server/batcher state is per-instance, but the events ring and metrics
registry are process-global: events are cleared per test and counter
assertions use BEFORE/AFTER deltas like tests/test_health.py.
"""

import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import events as oe
from paddle_tpu.serving import (Batcher, BucketPolicy, Engine,
                                QueueFullError, RequestTimeout,
                                ServerClosed, Server, ServingConfig,
                                common_batch)


@pytest.fixture(autouse=True)
def _clean_events():
    oe.clear()
    yield
    oe.clear()


def _infer_compiles():
    return [e for e in oe.recent(n=1000, kind="compile")
            if e.get("compile_kind") == "infer"]


def _post(url, payload, timeout=30):
    """(status, parsed body) — 4xx/5xx come back as values."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# Bucket policy
# ---------------------------------------------------------------------------


def test_bucket_policy_defaults_and_selection():
    p = BucketPolicy(max_batch=64)
    assert p.buckets == (1, 2, 4, 8, 16, 32, 64)
    assert p.max_batch == 64
    assert [p.bucket_for(n) for n in (1, 2, 3, 5, 64)] == [1, 2, 4, 8, 64]
    assert p.bucket_for(65) is None
    with pytest.raises(ValueError):
        p.bucket_for(0)


def test_bucket_policy_custom_and_validation():
    assert BucketPolicy(buckets=[4, 1, 4, 16]).buckets == (1, 4, 16)
    assert BucketPolicy(max_batch=6).buckets == (1, 2, 4, 6)
    with pytest.raises(ValueError):
        BucketPolicy(buckets=[0, 2])
    with pytest.raises(ValueError):
        BucketPolicy(max_batch=0)


def test_pad_slice_roundtrip():
    p = BucketPolicy(max_batch=8)
    arr = np.arange(12, dtype="float32").reshape(3, 4)
    padded = p.pad_batch(arr, 8)
    assert padded.shape == (8, 4)
    np.testing.assert_array_equal(padded[:3], arr)
    # edge padding: every pad row repeats the last real row
    np.testing.assert_array_equal(padded[3:], np.repeat(arr[-1:], 5, 0))
    np.testing.assert_array_equal(p.slice_batch(padded, 3), arr)
    assert p.pad_batch(arr, 3) is arr  # no copy when already sized
    with pytest.raises(ValueError):
        p.pad_batch(arr, 2)


def test_common_batch():
    assert common_batch({"a": np.zeros((3, 2)), "b": np.zeros((3,))}) == 3
    assert common_batch({"a": np.zeros((3, 2)),
                         "b": np.zeros((2, 2))}) is None
    assert common_batch({"a": np.float32(1.0)}) is None


# ---------------------------------------------------------------------------
# Batcher semantics on a fake engine (no jax, no model)
# ---------------------------------------------------------------------------


class _FakeEngine:
    """run_batch double: records dispatched row counts, optionally
    blocks on a gate or raises."""

    def __init__(self, gate=None, fail=False):
        self.calls = []
        self.gate = gate
        self.fail = fail
        self.entered = threading.Event()    # a batch has reached the engine

    def run_batch(self, feeds):
        self.entered.set()
        if self.gate is not None:
            assert self.gate.wait(20), "test gate never opened"
        if self.fail:
            raise RuntimeError("engine exploded")
        n = next(iter(feeds.values())).shape[0]
        self.calls.append(n)
        return {"y": np.concatenate([feeds[k] for k in sorted(feeds)],
                                    axis=-1) * 2.0}


def _submit_async(batcher, feeds, results, idx, timeout_s=None):
    def go():
        try:
            results[idx] = batcher.submit(feeds, timeout_s=timeout_s)
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            results[idx] = e

    t = threading.Thread(target=go, daemon=True)
    t.start()
    return t


def test_batcher_coalesces_concurrent_requests():
    eng = _FakeEngine()
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=8),
                max_wait_ms=250, timeout_s=10)
    try:
        results = {}
        xs = {i: np.full((n, 2), i, "float32")
              for i, n in ((0, 1), (1, 2), (2, 1))}
        threads = [_submit_async(b, {"x": xs[i]}, results, i)
                   for i in xs]
        for t in threads:
            t.join(timeout=20)
        # one dispatch carried all 4 rows (window open long enough)
        assert eng.calls == [4]
        for i in xs:
            np.testing.assert_array_equal(results[i]["y"], xs[i] * 2.0)
    finally:
        b.stop()


def test_batcher_full_bucket_dispatches_before_deadline():
    eng = _FakeEngine()
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=4),
                max_wait_ms=30_000, timeout_s=20)
    try:
        results = {}
        t0 = time.monotonic()
        threads = [_submit_async(b, {"x": np.zeros((1, 3), "float32")},
                                 results, i) for i in range(4)]
        for t in threads:
            t.join(timeout=20)
        # 4 rows = full bucket → dispatched without waiting out 30 s
        assert time.monotonic() - t0 < 10
        assert eng.calls == [4]
        assert all(isinstance(results[i], dict) for i in range(4))
    finally:
        b.stop()


def test_batcher_incompatible_signatures_not_coalesced():
    eng = _FakeEngine()
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=8),
                max_wait_ms=100, timeout_s=10)
    try:
        results = {}
        a = _submit_async(b, {"x": np.zeros((1, 4), "float32")}, results, 0)
        c = _submit_async(b, {"x": np.zeros((1, 8), "float32")}, results, 1)
        a.join(timeout=20)
        c.join(timeout=20)
        assert sorted(eng.calls) == [1, 1]  # two separate dispatches
        assert results[0]["y"].shape == (1, 4)
        assert results[1]["y"].shape == (1, 8)
    finally:
        b.stop()


def test_batcher_request_timeout():
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=4),
                max_wait_ms=1, timeout_s=10)
    try:
        # first request occupies the engine (gate closed) ...
        results = {}
        t1 = _submit_async(b, {"x": np.zeros((1, 2), "float32")},
                           results, 0)
        time.sleep(0.15)  # let it dispatch and block inside the engine
        # ... so the second request expires while queued
        t0 = time.monotonic()
        with pytest.raises(RequestTimeout):
            b.submit({"x": np.ones((1, 2), "float32")}, timeout_s=0.3)
        assert time.monotonic() - t0 < 5
    finally:
        gate.set()
        t1.join(timeout=20)
        b.stop()
    assert isinstance(results[0], dict)  # first request still completed


def test_batcher_backpressure_rejects_when_full():
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=1),
                max_queue=2, max_wait_ms=1, timeout_s=20)
    try:
        results = {}
        feeds = {"x": np.zeros((1, 2), "float32")}
        # the first is IN FLIGHT before the next two arrive: three at once
        # find a queue of two full whenever the worker has not yet taken
        # the first, and one of them is the request refused (a loaded
        # tier-1 run failed so)
        threads = [_submit_async(b, feeds, results, 0)]
        assert eng.entered.wait(10)
        threads += [_submit_async(b, feeds, results, i) for i in (1, 2)]
        deadline = time.monotonic() + 10
        while b.depth() < 2 and time.monotonic() < deadline:
            time.sleep(0.01)  # 1 in flight + 2 queued
        t0 = time.monotonic()
        with pytest.raises(QueueFullError):
            b.submit({"x": np.zeros((1, 2), "float32")})
        assert time.monotonic() - t0 < 1  # reject, not block
    finally:
        gate.set()
        for t in threads:
            t.join(timeout=20)
        b.stop()
    assert all(isinstance(results[i], dict) for i in range(3))


def test_batcher_engine_error_propagates():
    eng = _FakeEngine(fail=True)
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=4),
                max_wait_ms=1, timeout_s=10)
    try:
        with pytest.raises(RuntimeError, match="engine exploded"):
            b.submit({"x": np.zeros((1, 2), "float32")})
    finally:
        b.stop()


def test_batcher_drain_on_stop_and_reject_after():
    eng = _FakeEngine()
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=2),
                max_wait_ms=50, timeout_s=10)
    results = {}
    threads = [_submit_async(b, {"x": np.full((1, 2), i, "float32")},
                             results, i) for i in range(5)]
    time.sleep(0.02)
    b.stop()  # drain: everything already admitted completes
    for t in threads:
        t.join(timeout=20)
    assert all(isinstance(results[i], dict) for i in range(5)), results
    assert sum(eng.calls) == 5
    with pytest.raises(ServerClosed):
        b.submit({"x": np.zeros((1, 2), "float32")})
    b.stop()  # idempotent
    assert not b._thread.is_alive()


def test_batcher_non_batch_outputs_shared_not_sliced():
    """An output without the batch leading dim (scalar stats, per-class
    tensors) is handed whole to every caller — and a split that would
    once have crashed must not kill the batcher thread."""
    def run(feeds):
        n = next(iter(feeds.values())).shape[0]
        return {"y": np.ones((n, 2), "float32"),
                "loss": np.float32(0.5),           # 0-d
                "stats": np.zeros((7, 3), "float32")}  # fixed non-batch

    # declared batched-ness plumbed in (the Engine wires the Predictor's
    # _fetch_batched here): "stats" must come back whole even when its
    # leading dim COINCIDES with the dispatched row total (3+4=7 below)
    flags = {"y": True, "loss": False, "stats": False}
    b = Batcher(run, BucketPolicy(max_batch=8), max_wait_ms=100,
                timeout_s=10, output_batched=flags.get)
    try:
        results = {}
        threads = [_submit_async(b, {"x": np.zeros((n, 3), "float32")},
                                 results, i)
                   for i, n in enumerate((3, 4))]
        for t in threads:
            t.join(timeout=20)
        for i, n in enumerate((3, 4)):
            assert results[i]["y"].shape == (n, 2)
            assert results[i]["loss"] == np.float32(0.5)
            assert results[i]["stats"].shape == (7, 3)
        assert b._thread.is_alive()  # split path did not kill the loop
    finally:
        b.stop()


def test_batcher_oversize_request_rejected():
    eng = _FakeEngine()
    b = Batcher(eng.run_batch, BucketPolicy(max_batch=4),
                max_wait_ms=1, timeout_s=5)
    try:
        with pytest.raises(ValueError, match="largest bucket"):
            b.submit({"x": np.zeros((5, 2), "float32")})
    finally:
        b.stop()


# ---------------------------------------------------------------------------
# Predictor bucketing (satellite: recompile-per-batch-size fix)
# ---------------------------------------------------------------------------


def _save_softmax_model(tmp_path, rng, features=4, classes=3):
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[features], dtype="float32")
        pred = pt.layers.fc(input=x, size=classes, act="softmax")
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(8, features).astype("float32")
    ref = exe.run(main, feed={"x": X}, fetch_list=[pred])[0]
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                               main_program=main)
    return X, np.asarray(ref)


def test_predictor_bucketing_bounds_signatures(tmp_path, rng):
    X, ref = _save_softmax_model(tmp_path, rng)
    cfg = pt.AnalysisConfig(str(tmp_path))
    cfg.enable_bucketing(max_batch=8)
    p = pt.create_paddle_predictor(cfg)
    for bs in range(1, 9):
        out = list(p.predict(x=X[:bs]).values())[0]
        assert out.shape == (bs, 3)
        np.testing.assert_allclose(out, ref[:bs], atol=1e-5)
    # bs 1..8 → buckets {1,2,4,8}: 4 signatures, not 8
    assert len(p._cache) == 4


def test_predictor_unbucketed_unchanged(tmp_path, rng):
    X, ref = _save_softmax_model(tmp_path, rng)
    p = pt.create_paddle_predictor(pt.AnalysisConfig(str(tmp_path)))
    for bs in (1, 2, 3):
        np.testing.assert_allclose(
            list(p.predict(x=X[:bs]).values())[0], ref[:bs], atol=1e-5)
    assert len(p._cache) == 3  # exact-shape compile per batch size


def test_predictor_bucketing_ignores_non_batch_feeds(tmp_path, rng):
    """A feed with a fixed leading dim (weights, tables) must be neither
    counted toward the batch nor padded — even when its leading dim
    coincides with the request batch size."""
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[4], dtype="float32")
        w = pt.layers.data(name="w", shape=[4, 3], dtype="float32",
                           append_batch_size=False)
        out = pt.layers.matmul(x, w)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    pt.io.save_inference_model(str(tmp_path), ["x", "w"], [out], exe,
                               main_program=main)
    cfg = pt.AnalysisConfig(str(tmp_path))
    cfg.enable_bucketing(buckets=(8,))
    p = pt.create_paddle_predictor(cfg)
    X = rng.rand(4, 4).astype("float32")  # batch == w's leading dim
    W = rng.rand(4, 3).astype("float32")
    res = list(p.predict(x=X, w=W).values())[0]
    assert res.shape == (4, 3)  # x padded to 8 then sliced; w untouched
    np.testing.assert_allclose(res, X @ W, atol=1e-5)


def test_predictor_warm_compiles_ahead(tmp_path, rng):
    X, ref = _save_softmax_model(tmp_path, rng)
    cfg = pt.AnalysisConfig(str(tmp_path))
    cfg.enable_aot()
    cfg.enable_bucketing(buckets=(1, 2, 4))
    p = pt.create_paddle_predictor(cfg)
    for b in (1, 2, 4):
        assert p.warm(b)
    evs = _infer_compiles()
    assert len(evs) == 3
    # traffic across bs 1..4 adds no compiles and stays correct
    for bs in (1, 2, 3, 4):
        np.testing.assert_allclose(
            list(p.predict(x=X[:bs]).values())[0], ref[:bs], atol=1e-5)
    assert len(_infer_compiles()) == 3
    assert len(p._cache) == 3


# ---------------------------------------------------------------------------
# Live end-to-end server (acceptance)
# ---------------------------------------------------------------------------


def test_server_e2e_mixed_batches_bounded_compiles(tmp_path, rng):
    """Concurrent mixed-batch-size requests against a running Server
    return correct outputs while total XLA compiles stay ≤ the number of
    configured buckets (verified via compile events)."""
    X, ref = _save_softmax_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2, 4), max_wait_ms=10,
                        max_queue=64, timeout_s=30, use_tpu=False)
    server = Server(cfg)
    try:
        port = server.start(0)
        assert server.start() == port  # idempotent
        assert len(_infer_compiles()) == 3  # warmup compiled every bucket

        url = f"http://127.0.0.1:{port}/v1/predict"
        sizes = [1, 2, 3, 4, 1, 2, 3, 4]
        results = [None] * len(sizes)

        def fire(i, bs):
            results[i] = _post(url, {"feeds": {"x": X[:bs].tolist()}})

        threads = [threading.Thread(target=fire, args=(i, bs), daemon=True)
                   for i, bs in enumerate(sizes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)

        for (code, body), bs in zip(results, sizes):
            assert code == 200, body
            out = np.asarray(list(body["outputs"].values())[0])
            assert body["batch"] == bs
            np.testing.assert_allclose(out, ref[:bs], atol=1e-4)

        # served mixed batch sizes reused the bucketed signatures
        assert len(_infer_compiles()) == 3

        code, body = _get(f"http://127.0.0.1:{port}/v1/status")
        assert code == 200
        st = json.loads(body)
        assert st["queue_depth"] == 0
        assert st["buckets"] == [1, 2, 4]
        assert st["requests"]["ok"] >= len(sizes)
        assert sum(st["batches"].values()) >= 1

        # error paths over the wire
        code, _ = _get(f"http://127.0.0.1:{port}/nope")
        assert code == 404
        code, body = _post(url, {"no_feeds": True})
        assert code == 400
        code, body = _post(url, {"feeds": {"x": X[:5].tolist()}})
        assert code == 400  # exceeds largest bucket
        code, body = _post(url, {"feeds": {"bogus": [[1.0, 2.0]]}})
        assert code == 500  # engine failure is the server's fault
        assert "error" in body
    finally:
        server.stop()
    evs = oe.recent(n=50)
    assert any(e["kind"] == "serve_start" for e in evs)
    assert any(e["kind"] == "serve_stop" for e in evs)


def test_server_full_queue_rejects_503(tmp_path, rng):
    """Overload rejects with 503 instead of blocking: with the engine
    gated shut and max_queue=1, concurrent requests observably split
    into served vs rejected."""
    _save_softmax_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1,), max_wait_ms=1,
                        max_queue=1, timeout_s=30, use_tpu=False)
    server = Server(cfg)
    gate = threading.Event()
    orig = server._engine.run_batch

    def gated(feeds):
        assert gate.wait(30), "test gate never opened"
        return orig(feeds)

    server._engine.run_batch = gated
    try:
        port = server.start(0)
        url = f"http://127.0.0.1:{port}/v1/predict"
        codes = [None] * 6
        payload = {"feeds": {"x": [[0.1, 0.2, 0.3, 0.4]]}}

        def fire(i):
            codes[i] = _post(url, payload, timeout=60)[0]

        threads = [threading.Thread(target=fire, args=(i,), daemon=True)
                   for i in range(len(codes))]
        for t in threads:
            t.start()
            time.sleep(0.05)  # 1 in flight, 1 queued, rest rejected
        t0 = time.monotonic()
        deadline = t0 + 10
        while codes.count(None) > len(codes) - 3 and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        # rejections arrived while the engine was still gated shut —
        # admission control did not block behind the stuck batch
        assert codes.count(503) >= 1, codes
        gate.set()
        for t in threads:
            t.join(timeout=60)
        assert codes.count(200) >= 1, codes
        assert codes.count(200) + codes.count(503) == len(codes), codes
    finally:
        gate.set()
        server.stop()


def test_server_stop_leaves_no_threads_or_sockets(tmp_path, rng):
    """Bugfix satellite: stop() is idempotent and leaks neither serving
    threads nor the listening socket; no non-daemon thread survives."""
    _save_softmax_model(tmp_path, rng)
    non_daemon_before = {t.ident for t in threading.enumerate()
                         if not t.daemon}
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2), max_wait_ms=1,
                        use_tpu=False)
    server = Server(cfg)
    port = server.start(0)
    assert _get(f"http://127.0.0.1:{port}/v1/healthz")[0] == 200
    server.stop()
    server.stop()  # idempotent
    assert server.port() is None
    assert not [t for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("paddle-tpu-serving")]
    assert {t.ident for t in threading.enumerate()
            if not t.daemon} == non_daemon_before
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/v1/healthz",
                               timeout=2)
    # restartable after stop
    port2 = server2 = None
    try:
        server2 = Server(cfg)
        port2 = server2.start(0)
        assert _get(f"http://127.0.0.1:{port2}/v1/status")[0] == 200
    finally:
        if server2 is not None:
            server2.stop()


def test_server_bind_failure_leaks_nothing(tmp_path, rng):
    """start() against a taken port raises without leaking the batcher
    thread, and the failed server's stop() is safe."""
    _save_softmax_model(tmp_path, rng)
    cfg_a = ServingConfig(str(tmp_path), buckets=(1,), use_tpu=False)
    a = Server(cfg_a)
    port = a.start(0)
    try:
        before = {t.ident for t in threading.enumerate() if t.is_alive()}
        cfg_b = ServingConfig(str(tmp_path), buckets=(1,), port=port,
                              use_tpu=False)
        b = Server(cfg_b)
        with pytest.raises(OSError):
            b.start()
        b.stop()
        leaked = [t.name for t in threading.enumerate()
                  if t.is_alive() and t.ident not in before]
        assert not leaked, leaked
    finally:
        a.stop()


def test_server_status_counts_are_per_server(tmp_path, rng):
    """Outcome counters are process-global metrics; /v1/status and
    serve_stop must still report THIS server's traffic only."""
    X, _ = _save_softmax_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2), max_wait_ms=1,
                        use_tpu=False)
    for expect in (3, 1):  # two sequential servers, different traffic
        server = Server(cfg)
        try:
            port = server.start(0)
            url = f"http://127.0.0.1:{port}/v1/predict"
            for _ in range(expect):
                code, _body = _post(url, {"feeds": {"x": X[:1].tolist()}})
                assert code == 200
            st = json.loads(_get(f"http://127.0.0.1:{port}/v1/status")[1])
            assert st["requests"]["ok"] == expect
        finally:
            server.stop()
        stop_ev = [e for e in oe.recent(n=20, kind="serve_stop")][-1]
        assert stop_ev["ok"] == expect


def test_engine_overrides_external_predictor_policy(tmp_path, rng):
    """A handed-in predictor with its own (different) bucketing gets the
    engine's policy, so warmup and live traffic agree on signatures."""
    from paddle_tpu.serving import Engine

    X, ref = _save_softmax_model(tmp_path, rng)
    acfg = pt.AnalysisConfig(str(tmp_path))
    acfg.enable_bucketing(max_batch=64)  # would bucket bs=3 to 4
    pred = pt.create_paddle_predictor(acfg)
    eng = Engine(ServingConfig(str(tmp_path), buckets=(3, 6),
                               use_tpu=False), predictor=pred)
    assert pred.config._bucketing is eng.policy
    eng.warmup()
    assert len(_infer_compiles()) == 2  # exactly the engine's buckets
    out = eng.run_batch({"x": X[:2]})
    np.testing.assert_allclose(list(out.values())[0], ref[:2], atol=1e-5)
    assert len(_infer_compiles()) == 2  # bs=2 rode the warmed bucket 3


# ---------------------------------------------------------------------------
# Warmstart artifact (serialized bucket executables; ISSUE 6)
# ---------------------------------------------------------------------------


def test_warmstart_export_load_roundtrip(tmp_path, rng):
    """bake → boot: a fresh engine adopting the artifact serves every
    bucket with ZERO compile events, and replies are bit-identical to
    the engine that compiled from scratch."""
    X, _ = _save_softmax_model(tmp_path / "model", rng)
    art = str(tmp_path / "warm.bin")
    cfg = ServingConfig(str(tmp_path / "model"), buckets=(1, 2, 4),
                        use_tpu=False)
    eng = Engine(cfg)
    assert eng.warmup() == 3
    assert eng.export_warmstart(art) == 3
    out_cold = eng.run_batch({"x": X[:3]})

    seq0 = oe.recent()[-1]["seq"] if oe.recent() else -1
    eng2 = Engine(ServingConfig(str(tmp_path / "model"),
                                buckets=(1, 2, 4), use_tpu=False,
                                warmstart=art))
    assert eng2.warmstart_adopted == 3
    assert eng2.warmup() == 3  # no-op: every bucket already AOT
    out_warm = eng2.run_batch({"x": X[:3]})
    new = [e for e in oe.recent() if e["seq"] > seq0]
    assert not [e for e in new if e["kind"] == "compile"], \
        "warmstart boot must not compile"
    assert eng2.status()["warmstart_adopted"] == 3
    k = list(out_cold)[0]
    np.testing.assert_array_equal(out_cold[k], out_warm[k])


def test_warmstart_rejects_different_model(tmp_path, rng):
    """An artifact baked from a DIFFERENT program must be rejected via
    the model digest — same signatures, different computation is the
    silent-wrong-answers failure mode."""
    _save_softmax_model(tmp_path / "m1", rng)
    _save_softmax_model(tmp_path / "m2", rng, classes=5)
    art = str(tmp_path / "warm.bin")
    eng1 = Engine(ServingConfig(str(tmp_path / "m1"), buckets=(1, 2),
                                use_tpu=False))
    eng1.warmup()
    assert eng1.export_warmstart(art) == 2
    eng2 = Engine(ServingConfig(str(tmp_path / "m2"), buckets=(1, 2),
                                use_tpu=False, warmstart=art))
    assert eng2.warmstart_adopted == 0
    rejects = [e for e in oe.recent() if e["kind"] == "warmstart"
               and e.get("action") == "reject"]
    assert rejects and "digest" in rejects[-1]["reason"]
    assert eng2.warmup() == 2  # degraded to a normal compile warmup


def test_warmstart_rejects_stale_lowering_fingerprint(tmp_path, rng):
    """Every entry embeds its signature's lowering fingerprint, and
    adoption re-lowers to verify it: an artifact baked before a
    paddle_tpu lowering change (same jax/backend/model digest!) must
    fall back to compiling that bucket, never serve the old
    computation. Simulated by tampering with one stored fingerprint."""
    import pickle

    _save_softmax_model(tmp_path / "model", rng)
    art = str(tmp_path / "warm.bin")
    eng1 = Engine(ServingConfig(str(tmp_path / "model"), buckets=(1, 2),
                                use_tpu=False))
    eng1.warmup()
    assert eng1.export_warmstart(art) == 2
    with open(art, "rb") as f:
        blob = pickle.loads(f.read())
    sig = next(iter(blob["entries"]))
    blob["entries"][sig]["fingerprint"] = "0" * 64
    with open(art, "wb") as f:  # atomic-exempt: test fixture tamper
        f.write(pickle.dumps(blob))
    eng2 = Engine(ServingConfig(str(tmp_path / "model"), buckets=(1, 2),
                                use_tpu=False, warmstart=art))
    assert eng2.warmstart_adopted == 1  # the untampered entry only
    assert eng2.warmup() == 2  # tampered bucket compiled normally


def test_warmstart_rejects_garbage_artifact(tmp_path, rng):
    _save_softmax_model(tmp_path / "model", rng)
    art = tmp_path / "warm.bin"
    art.write_bytes(b"definitely not a pickle")
    eng = Engine(ServingConfig(str(tmp_path / "model"), buckets=(1,),
                               use_tpu=False, warmstart=str(art)))
    assert eng.warmstart_adopted == 0
    assert eng.warmup() == 1


def test_warmstart_missing_artifact_emits_reject(tmp_path, rng):
    """A typo'd warmstart path boots the fleet cold — that must leave
    a reject event in the log, not just a silent adopted=0."""
    _save_softmax_model(tmp_path / "model", rng)
    eng = Engine(ServingConfig(str(tmp_path / "model"), buckets=(1,),
                               use_tpu=False,
                               warmstart=str(tmp_path / "nope.warm")))
    assert eng.warmstart_adopted == 0
    rejects = [e for e in oe.recent() if e["kind"] == "warmstart"
               and e.get("action") == "reject"]
    assert rejects and "unreadable" in rejects[-1]["reason"]
    assert eng.warmup() == 1  # degraded to a normal compile warmup


@pytest.mark.slow
def test_warmstart_tool_bake_inspect(tmp_path, rng):
    """tools/warmstart.py CLI: bake writes a loadable artifact and
    prints its summary; inspect reads it back without jax."""
    import os
    import subprocess

    _save_softmax_model(tmp_path / "model", rng)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    art = str(tmp_path / "warm.bin")
    tool = os.path.join(repo, "tools", "warmstart.py")
    proc = subprocess.run(
        [sys.executable, tool, "bake", "--model-dir",
         str(tmp_path / "model"), "--out", art, "--buckets", "1,2,4",
         "--cpu"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["entries"] == 3 and summary["buckets"] == [1, 2, 4]
    proc = subprocess.run([sys.executable, tool, "inspect", art],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    info = json.loads(proc.stdout)
    assert info["entries"] == 3 and info["backend"] == "cpu"
    assert all(s["blob_bytes"] > 0 for s in info["signatures"])
    # and the engine can boot from the CLI-baked artifact
    eng = Engine(ServingConfig(str(tmp_path / "model"),
                               buckets=(1, 2, 4), use_tpu=False,
                               warmstart=art))
    assert eng.warmstart_adopted == 3


# ---------------------------------------------------------------------------
# Fleet satellites (ISSUE 14): healthz states, /v1/load probe, drain
# ---------------------------------------------------------------------------


def test_healthz_reports_state_and_load_probe(tmp_path, rng):
    X, _ = _save_softmax_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2), use_tpu=False,
                        max_wait_ms=1.0)
    server = Server(cfg)
    assert server.state() == "stopped"
    port = server.start(0)
    base = f"http://127.0.0.1:{port}"
    try:
        code, body = _get(base + "/v1/healthz")
        assert code == 200 and json.loads(body)["state"] == "serving"
        code, body = _get(base + "/v1/load")
        probe = json.loads(body)
        assert code == 200
        assert set(probe) == {"load", "inflight", "queue_depth",
                              "state", "models"}
        assert probe["load"] == 0.0 and probe["state"] == "serving"
        # the model advertisement the router's model-aware picks read
        assert probe["models"] == ["default"]
        # /v1/status carries the same fields for the full view
        code, body = _post(base + "/v1/predict",
                           {"feeds": {"x": X[:1].tolist()}})
        assert code == 200
        code, body = _get(base + "/v1/status")
        st = json.loads(body)
        assert st["state"] == "serving" and "load" in st \
            and "inflight" in st
    finally:
        server.stop()
    assert server.state() == "stopped"


def test_state_warming_until_buckets_warm(tmp_path, rng):
    """The health probe must not admit a replica whose bucket grid is
    still compiling: state() is 'warming' while started-but-unwarmed
    (the fleet router treats anything but 'serving' as unhealthy)."""
    _save_softmax_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1,), use_tpu=False)
    server = Server(cfg)
    # start() warms before binding, so the warming window is normally
    # invisible over HTTP; drive the state machine directly
    server._started_t = time.monotonic()
    assert server._engine.warmed is False
    assert server.state() == "warming"
    server._engine.warmup()
    assert server.state() == "serving"
    server._started_t = None
    assert server.state() == "stopped"


def test_drain_rejects_with_retry_after_and_finishes_inflight(
        tmp_path, rng):
    """Scale-in semantics: drain() keeps the listener up, finishes the
    queued work, 503s new predicts WITH Retry-After, healthz flips to
    503 draining — and stop() afterwards tears down cleanly."""
    X, _ = _save_softmax_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2), use_tpu=False,
                        max_wait_ms=20.0, timeout_s=30.0)
    server = Server(cfg)
    port = server.start(0)
    base = f"http://127.0.0.1:{port}"
    results = []

    def fire():
        results.append(_post(base + "/v1/predict",
                             {"feeds": {"x": X[:1].tolist()}}))

    # in-flight work submitted BEFORE the drain must complete (the
    # coalescing window of max_wait_ms=20 keeps it queued long enough
    # for drain() to start while it is pending)
    th = threading.Thread(target=fire)
    th.start()
    # wait until the request IS queued or in flight (or already answered):
    # a fixed sleep raced the request thread against drain() under load,
    # and a request that arrives after the drain gets the 503
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and not results \
            and not server.load()["load"]:
        time.sleep(0.001)
    server.drain(timeout=30.0)
    th.join(timeout=30)
    assert results and results[0][0] == 200
    assert server.state() == "draining"
    # new predicts: 503 + Retry-After over the still-up listener
    req = urllib.request.Request(
        base + "/v1/predict",
        data=json.dumps({"feeds": {"x": X[:1].tolist()}}).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 503
    assert ei.value.headers.get("Retry-After") == "1"
    code, body = _get(base + "/v1/healthz")
    assert code == 503 and json.loads(body)["state"] == "draining"
    drains = [e for e in oe.recent(100) if e["kind"] == "serve_drain"]
    assert len(drains) == 1
    server.drain()  # idempotent
    assert len([e for e in oe.recent(100)
                if e["kind"] == "serve_drain"]) == 1
    server.stop()
    assert server.port() is None


def test_batcher_inflight_counts_dispatched_requests():
    """inflight() covers the queue→engine gap: while a batch executes,
    the load probe must report its rows as in-flight, not zero."""
    import queue as _q

    release = threading.Event()
    seen = _q.Queue()

    def slow_engine(feeds):
        seen.put(True)
        release.wait(10.0)
        return {"y": feeds["x"]}

    b = Batcher(slow_engine, BucketPolicy(max_batch=4), max_wait_ms=1.0)
    try:
        th = threading.Thread(
            target=lambda: b.submit({"x": np.ones((1, 2))}))
        th.start()
        seen.get(timeout=10)      # engine is now holding the batch
        assert b.inflight() == 1
        assert b.depth() == 0     # left the queue
        release.set()
        th.join(timeout=10)
        deadline = time.monotonic() + 5
        while b.inflight() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert b.inflight() == 0
    finally:
        release.set()
        b.stop()
