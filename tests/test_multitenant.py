"""Multi-tenant QoS + multi-model fleet tests (ISSUE 19): weighted-
fair share math on the start-time fair scheduler, tier-ordered shed
selection, batcher admission integration (queue shed + quota) on a
fake engine, the content-addressed model registry (digest-mismatch
rejection, blob verification), zero-downtime hot-swap with in-flight
HTTP traffic (bit-identical outputs, zero failed requests), the
router's model-id routing and its shed-is-an-answer contract against
fake replicas, and the per-tenant metric/trace evidence.

Metrics are process-global, so counter assertions use BEFORE/AFTER
deltas; the events ring is cleared per test (test_serving idiom).
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import events as oe
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import tracing as ot
from paddle_tpu.serving import (Batcher, BucketPolicy, Engine,
                                ModelRegistry, QoSPolicy, RegistryError,
                                Router, RouterServer, Server,
                                ServingConfig, ShedError, TenantSpec,
                                TierShed, WeightedFairScheduler)
from paddle_tpu.serving import qos as qos_mod
from paddle_tpu.serving import router as router_mod
from paddle_tpu.serving.qos import shed_victim


@pytest.fixture(autouse=True)
def _clean_events():
    oe.clear()
    yield
    oe.clear()


def _post(url, payload, timeout=30):
    """(status, parsed body, headers) — 4xx/5xx come back as values."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


# ---------------------------------------------------------------------------
# QoSPolicy + weighted-fair share math (pure python, no jax)
# ---------------------------------------------------------------------------


def _policy(**tenants):
    return QoSPolicy(
        tiers=("gold", "silver", "bronze"), default_tier="bronze",
        tenants={k: TenantSpec(**v) for k, v in tenants.items()})


def test_policy_from_spec_roundtrip_and_validation():
    spec = {"tiers": ["gold", "bronze"], "default_tier": "bronze",
            "tenants": {"acme": {"tier": "gold", "weight": 3,
                                 "max_inflight": 8}}}
    pol = QoSPolicy.from_spec(spec)
    assert pol.tier_of("acme") == "gold"
    assert pol.tier_of("nobody") == "bronze"
    assert pol.weight_of("acme") == 3.0
    assert pol.quota_of("acme") == 8
    assert pol.quota_of("nobody") is None
    # rank: 0 = highest; unknown tiers rank below everything
    assert pol.rank_of("acme") < pol.rank_of("nobody")
    assert pol.tier_rank("mystery") == len(pol.tiers)
    # spec_dict is the from_spec shape again
    assert QoSPolicy.from_spec(pol.spec_dict()).tier_of("acme") == "gold"
    assert QoSPolicy.from_spec(None) is None
    assert QoSPolicy.from_spec(pol) is pol
    with pytest.raises(ValueError):
        QoSPolicy(tiers=())
    with pytest.raises(ValueError):
        QoSPolicy(tiers=("a", "a"))
    with pytest.raises(ValueError):
        QoSPolicy(tiers=("a",), default_tier="b")
    with pytest.raises(ValueError):
        QoSPolicy(tiers=("a",),
                  tenants={"t": TenantSpec(tier="nope")})
    with pytest.raises(ValueError):
        TenantSpec(weight=0)


def test_wfq_weights_give_proportional_shares():
    """Two always-backlogged tenants with weights 3:1 split service
    3:1 — exactly, since the scheduler is deterministic."""
    pol = _policy(a={"weight": 3.0}, b={"weight": 1.0})
    sched = WeightedFairScheduler(pol, clock=lambda: 0.0)
    for _ in range(400):
        i = sched.pick(["a", "b"])
        sched.charge(["a", "b"][i], 1.0)
    assert sched.served("a") == 300.0
    assert sched.served("b") == 100.0
    shares = sched.served_shares()
    assert shares["a"] == pytest.approx(0.75)


def test_wfq_strict_tier_priority_across_tiers():
    """A gold candidate always beats bronze regardless of how much
    service gold has already consumed: priority is strict across
    tiers, fairness only applies within one."""
    pol = _policy(vip={"tier": "gold"})
    sched = WeightedFairScheduler(pol, clock=lambda: 0.0)
    sched.charge("vip", 1e6)            # vast virtual-time lead
    for _ in range(10):
        assert sched.pick(["other", "vip"]) == 1
        sched.charge("vip", 1.0)


def test_wfq_idle_tenant_gets_no_banked_credit():
    """A tenant returning from idle starts at the system virtual time:
    it does not monopolize the scheduler to 'catch up' on service it
    never requested (the SFQ backlogged-fairness property)."""
    pol = _policy(a={"weight": 1.0}, b={"weight": 1.0})
    sched = WeightedFairScheduler(pol, clock=lambda: 0.0)
    for _ in range(100):                # a runs alone; b idle
        sched.pick(["a"])
        sched.charge("a", 1.0)
    for _ in range(100):                # b arrives backlogged
        i = sched.pick(["a", "b"])
        sched.charge(["a", "b"][i], 1.0)
    # equal weights → the contended window splits ~50/50; b must NOT
    # take (nearly) all 100 on banked idle credit
    assert 40.0 <= sched.served("b") <= 60.0
    assert sched.served("a") >= 140.0


def test_wfq_pick_rejects_empty():
    sched = WeightedFairScheduler(_policy(), clock=lambda: 0.0)
    with pytest.raises(ValueError):
        sched.pick([])


# ---------------------------------------------------------------------------
# Shed victim selection
# ---------------------------------------------------------------------------


def test_shed_victim_lowest_tier_newest_first():
    pol = _policy(vip={"tier": "gold"}, mid={"tier": "silver"})
    # queued: gold(1), bronze(2), bronze(3); arrival gold(4)
    # → newest bronze (index 2) is shed, never the gold arrival
    entries = [("vip", 1), ("noisy", 2), ("noisy", 3)]
    assert shed_victim(entries + [("vip", 4)], pol) == 2
    # within one tier the NEWEST goes first
    assert shed_victim([("noisy", 2), ("noisy", 3), ("noisy", 1)],
                       pol) == 1
    # the arrival itself is the victim when it is the lowest tier
    assert shed_victim([("vip", 1), ("mid", 2), ("noisy", 3)], pol) == 2
    with pytest.raises(ValueError):
        shed_victim([], pol)


# ---------------------------------------------------------------------------
# Batcher QoS admission on a fake engine (no jax)
# ---------------------------------------------------------------------------


class _FakeEngine:
    def __init__(self, gate=None):
        self.calls = []
        self.gate = gate
        self.started = threading.Event()   # a dispatch reached us

    def run_batch(self, feeds):
        self.started.set()
        if self.gate is not None:
            assert self.gate.wait(20), "test gate never opened"
        n = next(iter(feeds.values())).shape[0]
        self.calls.append(n)
        return {"y": feeds["x"] * 2.0}


def _submit_async(batcher, feeds, results, idx, tenant=None):
    def go():
        try:
            results[idx] = batcher.submit(feeds, tenant=tenant)
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            results[idx] = e

    t = threading.Thread(target=go, daemon=True)
    t.start()
    return t


def _wait_for(pred, timeout=10.0, msg="condition"):
    dl = time.monotonic() + timeout
    while time.monotonic() < dl:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


_QOS_SPEC = {"tiers": ["gold", "bronze"], "default_tier": "bronze",
             "tenants": {"vip": {"tier": "gold", "weight": 4},
                         "capped": {"max_inflight": 1}}}


def test_batcher_queue_full_sheds_lowest_tier_not_arrival():
    """Queue full + gold arrival: a QUEUED bronze request is woken
    with ShedError and the gold arrival is admitted in its place."""
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    b = Batcher(eng.run_batch, BucketPolicy(buckets=(1,)),
                max_queue=2, max_wait_ms=1, timeout_s=15,
                qos=_QOS_SPEC)
    before = qos_mod.SHEDS.value(tier="bronze", kind="queue")
    x = {"x": np.ones((1, 2), "float32")}
    results = {}
    try:
        # 0 dispatches and blocks in the engine; 1..2 fill the queue
        _submit_async(b, x, results, 0, tenant="noisy")
        _wait_for(eng.started.is_set, msg="dispatch")
        _submit_async(b, x, results, 1, tenant="noisy")
        _submit_async(b, x, results, 2, tenant="noisy")
        _wait_for(lambda: b.depth() == 2, msg="queue to fill")
        t3 = _submit_async(b, x, results, 3, tenant="vip")
        # the newest queued bronze (request 2) is shed immediately
        _wait_for(lambda: isinstance(results.get(2), ShedError),
                  msg="bronze victim shed")
        assert results[2].tier == "bronze"
        assert results[2].kind == "queue"
        assert results[2].retry_after_s > 0
        gate.set()
        t3.join(timeout=20)
        assert isinstance(results[3], dict)      # gold was admitted
        np.testing.assert_allclose(results[3]["y"], x["x"] * 2.0)
    finally:
        gate.set()
        b.stop()
    assert qos_mod.SHEDS.value(tier="bronze", kind="queue") \
        == before + 1
    evs = [e for e in oe.recent(n=100, kind="shed")]
    assert any(e.get("tier") == "bronze" and e.get("shed") == "queue"
               for e in evs)


def test_batcher_queue_full_bronze_arrival_is_its_own_victim():
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    b = Batcher(eng.run_batch, BucketPolicy(buckets=(1,)),
                max_queue=1, max_wait_ms=1, timeout_s=15,
                qos=_QOS_SPEC)
    x = {"x": np.ones((1, 2), "float32")}
    results = {}
    try:
        _submit_async(b, x, results, 0, tenant="vip")
        _wait_for(eng.started.is_set, msg="dispatch")
        _submit_async(b, x, results, 1, tenant="vip")
        _wait_for(lambda: b.depth() == 1, msg="queue to fill")
        with pytest.raises(ShedError) as ei:
            b.submit(x, tenant="noisy")
        assert ei.value.tier == "bronze"
        assert ei.value.tenant == "noisy"
        gate.set()
        # the submitting threads store their results after the batcher
        # answers: wait for both (stop() does not join them)
        _wait_for(lambda: isinstance(results.get(0), dict)
                  and isinstance(results.get(1), dict),
                  msg="both vip requests to finish")
    finally:
        gate.set()
        b.stop()


def test_batcher_quota_caps_concurrent_footprint():
    """max_inflight bounds one tenant's queued+dispatched total even
    with a near-empty queue; the rejection is a typed quota shed."""
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    b = Batcher(eng.run_batch, BucketPolicy(buckets=(1,)),
                max_queue=64, max_wait_ms=1, timeout_s=15,
                qos=_QOS_SPEC)
    before = qos_mod.SHEDS.value(tier="bronze", kind="quota")
    x = {"x": np.ones((1, 2), "float32")}
    results = {}
    try:
        _submit_async(b, x, results, 0, tenant="capped")
        _wait_for(eng.started.is_set, msg="dispatch")
        with pytest.raises(ShedError) as ei:
            b.submit(x, tenant="capped")
        assert ei.value.kind == "quota"
        assert ei.value.tenant == "capped"
        # other tenants are unaffected by capped's quota
        _submit_async(b, x, results, 1, tenant="noisy")
        gate.set()
        _wait_for(lambda: isinstance(results.get(0), dict)
                  and isinstance(results.get(1), dict),
                  msg="both tenants to finish")
    finally:
        gate.set()
        b.stop()
    assert qos_mod.SHEDS.value(tier="bronze", kind="quota") \
        == before + 1


def test_batcher_per_tenant_metrics_and_trace_tags():
    """Successful requests under a QoS policy land per-tenant outcome
    counters, and the queue-wait span carries the tenant tag when the
    caller's trace is sampled."""
    eng = _FakeEngine()
    b = Batcher(eng.run_batch, BucketPolicy(buckets=(1, 2)),
                max_wait_ms=1, timeout_s=15, qos=_QOS_SPEC)
    before_ok = qos_mod.TENANT_REQUESTS.value(
        tenant="acme", tier="bronze", outcome="ok")
    ot.clear_spans()
    try:
        with ot.activate(ot.start_trace(sampled=True)):
            out = b.submit({"x": np.ones((1, 2), "float32")},
                           tenant="acme")
        assert out["y"].shape == (1, 2)
    finally:
        b.stop()
    assert qos_mod.TENANT_REQUESTS.value(
        tenant="acme", tier="bronze", outcome="ok") == before_ok + 1
    waits = [s for s in ot.get_spans()
             if s.name == "serve.queue_wait"
             and (s.args or {}).get("tenant") == "acme"]
    assert waits, "sampled queue-wait span must carry the tenant tag"


def test_batcher_without_qos_keeps_legacy_queuefull():
    """No policy → historical single-tenant behavior: queue overflow
    raises plain QueueFullError for the arrival, no shed metrics."""
    from paddle_tpu.serving import QueueFullError
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    b = Batcher(eng.run_batch, BucketPolicy(buckets=(1,)),
                max_queue=1, max_wait_ms=1, timeout_s=15)
    x = {"x": np.ones((1, 2), "float32")}
    results = {}
    try:
        _submit_async(b, x, results, 0)
        _wait_for(eng.started.is_set, msg="dispatch")
        _submit_async(b, x, results, 1)
        _wait_for(lambda: b.depth() == 1, msg="queue to fill")
        with pytest.raises(QueueFullError) as ei:
            b.submit(x)
        assert not isinstance(ei.value, ShedError)
        gate.set()
    finally:
        gate.set()
        b.stop()


# ---------------------------------------------------------------------------
# Model registry: publish / resolve / digest safety (CPU jax)
# ---------------------------------------------------------------------------


def _save_model(dirpath, rng, size=3):
    """A tiny inference model; `size` changes the program structure so
    two saves get DIFFERENT __model__ digests (same-topology programs
    are byte-identical up to weights, which live in separate files)."""
    os.makedirs(str(dirpath), exist_ok=True)
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), \
            pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[4], dtype="float32")
        pred = pt.layers.fc(input=x, size=size, act="softmax")
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(6, 4).astype("float32")
    ref = exe.run(main, feed={"x": X}, fetch_list=[pred])[0]
    pt.io.save_inference_model(str(dirpath), ["x"], [pred], exe,
                               main_program=main)
    return X, np.asarray(ref)


def test_registry_publish_resolve_and_versions(tmp_path, rng):
    dir_a = tmp_path / "model_a"
    _save_model(dir_a, rng)
    eng = Engine(ServingConfig(str(dir_a), buckets=(1, 2),
                               use_tpu=False))
    eng.warmup()
    ws = str(tmp_path / "a.warmstart")
    eng.export_warmstart(ws)
    reg = ModelRegistry(str(tmp_path / "registry"))
    assert reg.version("m") is None
    e1 = reg.publish("m", ws, model_dir=str(dir_a))
    assert e1["version"] == 1
    assert reg.version("m") == 1
    e2 = reg.publish("m", ws, model_dir=str(dir_a))
    assert e2["version"] == 2            # versions are monotone
    got = reg.resolve("m")
    assert got["digest"] == e2["digest"]
    assert os.path.exists(got["path"])
    with pytest.raises(RegistryError):
        reg.resolve("never-published")
    with pytest.raises(RegistryError):
        reg.publish("m", str(tmp_path / "missing.warmstart"))


def test_registry_rejects_digest_mismatch_and_corrupt_blob(
        tmp_path, rng):
    """An artifact baked against program A must not publish for
    program B, and a blob whose bytes no longer match the manifest
    digest must not resolve."""
    dir_a, dir_b = tmp_path / "a", tmp_path / "b"
    _save_model(dir_a, rng, size=3)
    _save_model(dir_b, rng, size=5)      # structurally different
    eng = Engine(ServingConfig(str(dir_a), buckets=(1,),
                               use_tpu=False))
    eng.warmup()
    ws = str(tmp_path / "a.warmstart")
    eng.export_warmstart(ws)
    reg = ModelRegistry(str(tmp_path / "registry"))
    with pytest.raises(RegistryError, match="digest mismatch"):
        reg.publish("m", ws, model_dir=str(dir_b))
    entry = reg.publish("m", ws, model_dir=str(dir_a))
    with open(entry["path"], "ab") as f:
        f.write(b"torn")
    with pytest.raises(RegistryError, match="digest"):
        reg.resolve("m")


# ---------------------------------------------------------------------------
# Server: hot-swap under load, /v1/models, typed shed 503 (CPU jax)
# ---------------------------------------------------------------------------


def _compile_count():
    comp = om.snapshot().get("paddle_tpu_compile_seconds") \
        or {"series": []}
    return sum(s["count"] for s in comp["series"])


def test_server_hot_swap_zero_failed_requests_bit_identical(
        tmp_path, rng):
    """In-flight HTTP traffic across a hot_swap(): every request
    succeeds, the swapped engine (same program, adopted warmstart)
    answers bit-identically to the original, and the swap compiles
    nothing."""
    X, _unused = _save_model(tmp_path, rng)
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2, 4, 8),
                        max_wait_ms=1, use_tpu=False,
                        model_id="prod")
    server = Server(cfg)
    port = server.start(0)
    url = f"http://127.0.0.1:{port}/v1/predict"
    feeds = {"x": X.tolist()}
    try:
        st, body, _ = _post(url, {"feeds": feeds, "tenant": "acme"})
        assert st == 200
        ref = np.asarray(list(body["outputs"].values())[0])

        ws = str(tmp_path / "prod.warmstart")
        server._engine.export_warmstart(ws)
        stop = threading.Event()
        outcomes = []

        def hammer():
            while not stop.is_set():
                s, b, _ = _post(url, {"feeds": feeds})
                outcomes.append(
                    (s, np.asarray(list(b["outputs"].values())[0])
                     if s == 200 else None))

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.2)                  # traffic in flight
        compiles = _compile_count()
        rec = server.hot_swap(model_dir=str(tmp_path), warmstart=ws,
                              version=7)
        time.sleep(0.2)                  # traffic past the swap
        stop.set()
        for t in threads:
            t.join(timeout=20)

        assert _compile_count() == compiles, \
            "a swap onto an adopted warmstart must not compile"
        assert rec["warmstart_adopted"] > 0
        assert rec["model"] == "prod" and rec["version"] == 7
        assert outcomes, "hammer threads never completed a request"
        bad = [s for s, _ in outcomes if s != 200]
        assert not bad, f"hot swap failed {len(bad)} requests: {bad[:5]}"
        for _, out in outcomes:
            np.testing.assert_array_equal(out, ref)

        rows = {r["id"]: r for r in server.models()}
        assert rows["prod"]["version"] == 7
        assert rows["prod"]["warmstart_adopted"] > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models", timeout=10) as r:
            assert {m["id"] for m in json.loads(r.read())["models"]} \
                == {"prod"}
        evs = oe.recent(n=50, kind="model_swap")
        assert any(e.get("model") == "prod" and e.get("version") == 7
                   for e in evs)
    finally:
        server.stop()


def test_server_registry_watcher_adopts_published_version(
        tmp_path, rng):
    """A publish while serving is adopted by the watcher with no
    restart; a same-digest artifact on an already-warm engine records
    the version without a redundant swap."""
    _save_model(tmp_path / "model", rng)
    cfg = ServingConfig(str(tmp_path / "model"), buckets=(1, 2),
                        max_wait_ms=1, use_tpu=False, model_id="live")
    server = Server(cfg)
    server.start(0)
    try:
        ws = str(tmp_path / "live.warmstart")
        server._engine.export_warmstart(ws)
        reg = ModelRegistry(str(tmp_path / "registry"))
        server.attach_registry(reg, poll_s=0.05)
        entry = reg.publish("live", ws,
                            model_dir=str(tmp_path / "model"))
        _wait_for(lambda: any(r["id"] == "live"
                              and r["version"] == entry["version"]
                              for r in server.models()),
                  timeout=20, msg="watcher to adopt the publish")
    finally:
        server.stop()


def test_server_shed_maps_to_typed_503_with_retry_after(
        tmp_path, rng):
    """The HTTP contract for a shed: 503, Retry-After header, and a
    body naming the victim tier/kind — what the router classifies as
    an answer. A zero quota makes the shed deterministic."""
    _save_model(tmp_path, rng)
    qos = {"tiers": ["gold", "bronze"], "default_tier": "bronze",
           "tenants": {"blocked": {"max_inflight": 0}}}
    cfg = ServingConfig(str(tmp_path), buckets=(1, 2), max_wait_ms=1,
                        use_tpu=False, qos=qos)
    server = Server(cfg)
    port = server.start(0)
    before = qos_mod.SHEDS.value(tier="bronze", kind="quota")
    try:
        st, body, headers = _post(
            f"http://127.0.0.1:{port}/v1/predict",
            {"feeds": {"x": [[0.1, 0.2, 0.3, 0.4]]},
             "tenant": "blocked"})
        assert st == 503
        assert body["shed"] == "bronze"
        assert body["kind"] == "quota"
        assert body["tenant"] == "blocked"
        assert float(body["retry_after_s"]) > 0
        assert int(headers.get("Retry-After")) >= 1
        # other tenants keep flowing
        st2, body2, _ = _post(
            f"http://127.0.0.1:{port}/v1/predict",
            {"feeds": {"x": [[0.1, 0.2, 0.3, 0.4]]}, "tenant": "ok"})
        assert st2 == 200
    finally:
        server.stop()
    assert qos_mod.SHEDS.value(tier="bronze", kind="quota") \
        == before + 1


# ---------------------------------------------------------------------------
# Router: model-id routing + shed passthrough (fake replicas, no jax)
# ---------------------------------------------------------------------------


class _FakeHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def _j(self, code, obj, headers=None):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        cfg = self.server.cfg
        if self.path == "/v1/healthz":
            self._j(200, {"status": "ok", "state": "serving"})
        elif self.path == "/v1/load":
            load = {"load": cfg.get("load", 0.0), "inflight": 0,
                    "queue_depth": 0, "state": "serving"}
            if cfg.get("models") is not None:
                load["models"] = cfg["models"]
            self._j(200, load)

    def do_POST(self):
        cfg = self.server.cfg
        n = int(self.headers.get("Content-Length", 0))
        json.loads(self.rfile.read(n)) if n else {}
        self.server.hits.append(self.path)
        mode = cfg.get("predict", "ok")
        if mode == "ok":
            self._j(200, {"outputs": {"y": [cfg.get("tag", "?")]},
                          "batch": 1})
        elif mode == "shed":
            self._j(503, {"error": "queue full; shed tier 'bronze'",
                          "shed": "bronze", "kind": "queue",
                          "tenant": "noisy", "retry_after_s": 2.0},
                    headers={"Retry-After": "2"})
        elif mode == "busy":
            self._j(503, {"error": "queue full"},
                    headers={"Retry-After": "1"})
        elif mode == "no_model":
            self._j(404, {"error": "unknown model 'x'"})


class _Fake:
    def __init__(self, tag="A", **cfg):
        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), _FakeHandler)
        self.srv.daemon_threads = True
        self.srv.cfg = dict(tag=tag, **cfg)
        self.srv.hits = []
        threading.Thread(target=self.srv.serve_forever,
                         daemon=True).start()
        self.endpoint = f"127.0.0.1:{self.srv.server_address[1]}"

    @property
    def hits(self):
        return self.srv.hits

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


@pytest.fixture
def fakes():
    made = []

    def make(tag="A", **cfg):
        rep = _Fake(tag, **cfg)
        made.append(rep)
        return rep

    yield make
    for rep in made:
        rep.close()


def _router(*eps, **kw):
    kw.setdefault("poll_interval_s", 0.05)
    kw.setdefault("probe_timeout_s", 2.0)
    kw.setdefault("retries", 2)
    return Router([r.endpoint for r in eps], **kw)


def test_router_routes_by_model_id(fakes):
    a = fakes("A", models=["alpha"])
    b = fakes("B", models=["beta"])
    router = _router(a, b)
    try:
        router.poll_once()
        for _ in range(6):
            assert router.predict({"x": [1]}, model="beta")[
                "outputs"]["y"] == ["B"]
            assert router.predict({"x": [1]}, model="alpha")[
                "outputs"]["y"] == ["A"]
        # no replica advertises "gamma" → not routable at all
        from paddle_tpu.serving import NoReplicasError
        with pytest.raises(NoReplicasError):
            router.predict({"x": [1]}, model="gamma")
        # advertisements surface in the fleet status
        models = {r["endpoint"]: r["models"]
                  for r in router.status()["replicas"]}
        assert models[a.endpoint] == ["alpha"]
        assert models[b.endpoint] == ["beta"]
    finally:
        router.stop()


def test_router_unknown_model_404_fails_over(fakes):
    """A replica answering 404 unknown-model (stale advertisement) is
    excluded for the request and the router fails over — without a
    breaker penalty."""
    a = fakes("A", predict="no_model", load=0.0)   # preferred by load
    b = fakes("B", load=50.0)
    router = _router(a, b)
    before = router_mod.RETRIES.value(reason="no_model")
    try:
        router.poll_once()
        out = router.predict({"x": [1]})
        assert out["outputs"]["y"] == ["B"]
        assert "/v1/predict" in a.hits          # tried A first
        assert router_mod.RETRIES.value(reason="no_model") \
            == before + 1
        healthy = {r["endpoint"]: r["healthy"]
                   for r in router.status()["replicas"]}
        assert healthy[a.endpoint]              # not ejected
    finally:
        router.stop()


def test_router_shed_503_is_an_answer_not_a_failover(fakes):
    """A typed tier-shed 503 must NOT retry on the healthy sibling
    (that amplifies the overload being relieved): the router raises
    TierShed carrying the replica's body, records a fleet shed, and
    leaves the breaker unpunished."""
    a = fakes("A", predict="shed", load=0.0)    # preferred by load
    b = fakes("B", load=50.0)
    router = _router(a, b)
    before_shed = router_mod.FLEET_SHEDS.value(tier="bronze")
    before_busy = router_mod.RETRIES.value(reason="busy")
    try:
        router.poll_once()
        with pytest.raises(TierShed) as ei:
            router.predict({"x": [1]}, tenant="noisy")
        assert ei.value.tier == "bronze"
        assert ei.value.body["kind"] == "queue"
        assert ei.value.retry_after_s == pytest.approx(2.0)
        assert "/v1/predict" not in b.hits      # no failover
        assert router_mod.FLEET_SHEDS.value(tier="bronze") \
            == before_shed + 1
        assert router_mod.RETRIES.value(reason="busy") == before_busy
        # the breaker took no penalty: the replica is still routable
        # and a PLAIN busy 503 from it still fails over afterwards
        a.srv.cfg["predict"] = "busy"
        out = router.predict({"x": [1]})
        assert out["outputs"]["y"] == ["B"]
    finally:
        router.stop()


def test_router_server_propagates_shed_body_and_retry_after(fakes):
    """The front door forwards the typed shed unchanged: 503 + the
    replica's body + Retry-After derived from retry_after_s."""
    a = fakes("A", predict="shed")
    router = _router(a)
    front = RouterServer(router)
    port = front.start(0)
    try:
        router.poll_once()
        st, body, headers = _post(
            f"http://127.0.0.1:{port}/v1/predict",
            {"feeds": {"x": [1]}, "tenant": "noisy"})
        assert st == 503
        assert body["shed"] == "bronze"
        assert body["kind"] == "queue"
        assert body["tenant"] == "noisy"
        assert headers.get("Retry-After") == "2"
    finally:
        front.stop()
