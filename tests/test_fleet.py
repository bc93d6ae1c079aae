"""Serving fleet tier (ISSUE 14): router load balancing, health
ejection, breaker-gated retry failover, drain semantics, autoscaling,
and replica supervision.

Router behavior is tested against FAKE replica HTTP servers (stdlib,
controllable health/predict/stream behavior, no jax) so every failure
mode is deterministic and fast; the supervisor is driven with stand-in
child processes. No test here boots real replica processes
(`serving/replica.py`): ROADMAP names that gap.

The CircuitBreaker concurrency tests extend the PR 10 probe-leak fix to
the router's usage pattern: many router worker threads hammering one
endpoint must admit exactly ONE half-open probe, and a probe thread
that dies mid-call must release the slot.
"""

import json
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from paddle_tpu.observability import events as oe
from paddle_tpu.resilience.retry import CircuitBreaker
from paddle_tpu.serving.autoscale import Autoscaler
from paddle_tpu.serving.router import (FleetError, FleetTimeout,
                                       NoReplicasError, Router,
                                       RouterServer, StreamBrokenError)


# ---------------------------------------------------------------------------
# Fake replica: a stdlib HTTP server with scriptable behavior
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
            state = cfg.get("state", "serving")
            ok = cfg.get("healthy", True)
            self._j(200 if ok else 503,
                    {"status": "ok" if ok else "unavailable",
                     "state": state})
        elif self.path == "/v1/load":
            self._j(200, {"load": cfg.get("load", 0.0), "inflight": 0,
                          "queue_depth": 0,
                          "state": cfg.get("state", "serving")})
        elif self.path == "/v1/status":
            self._j(200, {"tag": cfg.get("tag"),
                          "warmstart_adopted": cfg.get("adopted", 0)})

    def do_POST(self):
        cfg = self.server.cfg
        n = int(self.headers.get("Content-Length", 0))
        payload = json.loads(self.rfile.read(n)) if n else {}
        self.server.hits.append(self.path)
        if self.path == "/v1/generate":
            self._generate(cfg, payload)
            return
        mode = cfg.get("predict", "ok")
        if mode == "ok":
            self._j(200, {"outputs": {"y": [cfg.get("tag", "?")]},
                          "batch": 1})
        elif mode == "busy":
            self._j(503, {"error": "queue full"},
                    headers={"Retry-After": "1"})
        elif mode == "bad_request":
            self._j(400, {"error": "ragged feeds"})
        elif mode == "deadline":
            self._j(504, {"error": "request timed out"})
        elif mode == "boom":
            self._j(500, {"error": "engine exploded"})
        elif mode == "hang":
            time.sleep(cfg.get("hang_s", 10.0))
            self._j(200, {"outputs": {"y": ["late"]}, "batch": 1})

    def _chunk(self, line):
        data = line.encode()
        self.wfile.write(f"{len(data):x}\r\n".encode())
        self.wfile.write(data)
        self.wfile.write(b"\r\n")
        self.wfile.flush()

    def _generate(self, cfg, payload):
        mode = cfg.get("generate", "ok")
        if mode == "busy":
            self._j(503, {"error": "decode queue full"})
            return
        if mode == "bad_request":
            self._j(400, {"error": "prompt token ids out of range"})
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        if mode == "die_before_token":
            # replica death after committing the stream but before any
            # token: clean socket close, NO done record
            self.wfile.flush()
            self.close_connection = True
            return
        n = int(payload.get("max_new_tokens", 4))
        kill_after = cfg.get("die_after_tokens")
        for i in range(n):
            self._chunk(json.dumps({"token": 100 + i}) + "\n")
            if kill_after is not None and i + 1 >= kill_after:
                self.close_connection = True
                return  # mid-stream death: tokens delivered, no done
        self._chunk(json.dumps({"done": True, "tokens": n,
                                "finish_reason": "length",
                                "ttft_ms": 1.0}) + "\n")
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()
        self.close_connection = True


class FakeReplica:
    def __init__(self, tag="A", **cfg):
        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), _FakeHandler)
        self.srv.daemon_threads = True
        self.srv.cfg = dict(tag=tag, **cfg)
        self.srv.hits = []
        self._t = threading.Thread(target=self.srv.serve_forever,
                                   daemon=True)
        self._t.start()
        self.endpoint = f"127.0.0.1:{self.srv.server_address[1]}"

    @property
    def cfg(self):
        return self.srv.cfg

    @property
    def hits(self):
        return self.srv.hits

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()
        self._t.join(timeout=5)


@pytest.fixture
def fakes():
    made = []

    def make(tag="A", **cfg):
        rep = FakeReplica(tag, **cfg)
        made.append(rep)
        return rep

    yield make
    for rep in made:
        rep.close()


def _router(*eps, **kw):
    kw.setdefault("poll_interval_s", 0.05)
    kw.setdefault("probe_timeout_s", 2.0)
    kw.setdefault("retries", 2)
    kw.setdefault("breaker_reset_s", 0.2)
    return Router([r.endpoint if isinstance(r, FakeReplica) else r
                   for r in eps], **kw)


# ---------------------------------------------------------------------------
# Routing: power-of-two-choices + load probe
# ---------------------------------------------------------------------------


def test_p2c_prefers_lower_load(fakes):
    a = fakes("A", load=0.0)
    b = fakes("B", load=50.0)
    router = _router(a, b)
    router.poll_once()
    tags = [router.predict({"x": [1]})["outputs"]["y"][0]
            for _ in range(16)]
    # with only two replicas p2c always compares both: the loaded one
    # is never picked while the idle one exists
    assert tags.count("A") == 16
    router.stop()


def test_load_cache_refreshes_on_poll(fakes):
    a = fakes("A", load=50.0)
    b = fakes("B", load=0.0)
    router = _router(a, b)
    router.poll_once()
    assert router.predict({"x": [1]})["outputs"]["y"][0] == "B"
    # load flips; the pick follows at the next poll
    a.cfg["load"], b.cfg["load"] = 0.0, 50.0
    router.poll_once()
    assert router.predict({"x": [1]})["outputs"]["y"][0] == "A"
    router.stop()


# ---------------------------------------------------------------------------
# Health ejection / readmission
# ---------------------------------------------------------------------------


def test_health_ejection_and_readmission(fakes):
    a = fakes("A")
    b = fakes("B")
    router = _router(a, b, eject_threshold=2)
    router.poll_once()
    assert len(router.healthy_endpoints()) == 2
    a.cfg["healthy"] = False  # healthz starts answering 503
    router.poll_once()        # strike 1
    assert a.endpoint in router.healthy_endpoints()
    router.poll_once()        # strike 2 -> ejected
    assert router.healthy_endpoints() == [b.endpoint]
    ejects = [e for e in oe.recent(200, kind="fleet")
              if e.get("action") == "eject"
              and e.get("endpoint") == a.endpoint]
    assert ejects
    # every pick avoids the ejected replica
    for _ in range(6):
        assert router.predict({"x": [1]})["outputs"]["y"][0] == "B"
    a.cfg["healthy"] = True   # probe passes again -> readmitted
    router.poll_once()
    assert len(router.healthy_endpoints()) == 2
    readmits = [e for e in oe.recent(200, kind="fleet")
                if e.get("action") == "readmit"
                and e.get("endpoint") == a.endpoint]
    assert readmits
    router.stop()


def test_draining_replica_is_ejected_by_state(fakes):
    a = fakes("A", state="draining", healthy=False)
    b = fakes("B")
    router = _router(a, b, eject_threshold=1)
    router.poll_once()
    assert router.healthy_endpoints() == [b.endpoint]
    st = router.status()
    rep = next(r for r in st["replicas"] if r["endpoint"] == a.endpoint)
    assert rep["state"] == "draining" and not rep["healthy"]
    router.stop()


# ---------------------------------------------------------------------------
# Retry failover
# ---------------------------------------------------------------------------


def test_failover_on_dead_replica_zero_client_failures(fakes):
    a = fakes("A")
    b = fakes("B")
    router = _router(a, b)
    router.poll_once()
    a.close()  # SIGKILL equivalent: connections now refused
    for _ in range(10):
        out = router.predict({"x": [1]})
        assert out["outputs"]["y"][0] == "B"
    st = router.status()
    assert st["requests"]["ok"] == 10 and st["requests"]["error"] == 0
    # request-path ejection: the corpse left the healthy set without
    # waiting for eject_threshold poll intervals
    assert router.healthy_endpoints() == [b.endpoint]
    assert st["retries"].get("connect", 0) >= 1
    router.stop()


def test_failover_on_replica_500(fakes):
    a = fakes("A", predict="boom")
    b = fakes("B")
    router = _router(a, b)
    router.poll_once()
    tags = {router.predict({"x": [1]})["outputs"]["y"][0]
            for _ in range(6)}
    assert tags == {"B"}
    assert router.status()["retries"].get("server_error", 0) >= 1
    router.stop()


def test_busy_replica_fails_over_without_breaker_penalty(fakes):
    a = fakes("A", predict="busy", load=0.0)
    b = fakes("B", load=100.0)  # p2c would prefer A; A rejects
    router = _router(a, b)
    router.poll_once()
    for _ in range(8):
        assert router.predict({"x": [1]})["outputs"]["y"][0] == "B"
    st = router.status()
    assert st["retries"].get("busy", 0) >= 8
    rep = next(r for r in st["replicas"] if r["endpoint"] == a.endpoint)
    # 503s are admission control, not failures: breaker stays closed
    assert rep["breaker"] == "closed" and rep["healthy"]
    router.stop()


def test_client_error_never_retries(fakes):
    a = fakes("A", predict="bad_request")
    b = fakes("B", predict="bad_request")
    router = _router(a, b)
    router.poll_once()
    with pytest.raises(ValueError):
        router.predict({"x": [1]})
    # deterministic rejection went to exactly one replica
    assert len(a.hits) + len(b.hits) == 1
    router.stop()


def test_deadline_504_never_retries(fakes):
    a = fakes("A", predict="deadline")
    router = _router(a)
    router.poll_once()
    with pytest.raises(FleetTimeout):
        router.predict({"x": [1]})
    assert len(a.hits) == 1
    router.stop()


def test_all_replicas_dead_raises_typed_error(fakes):
    a = fakes("A")
    router = _router(a)
    router.poll_once()
    a.close()
    with pytest.raises(FleetError):
        router.predict({"x": [1]})
    with pytest.raises(NoReplicasError):
        # now ejected: nothing admissible at all
        router.predict({"x": [1]})
    router.stop()


# ---------------------------------------------------------------------------
# Streamed generation: resubmit-from-scratch vs typed error
# ---------------------------------------------------------------------------


def test_stream_zero_tokens_resubmits_on_survivor(fakes):
    a = fakes("A", generate="die_before_token", load=0.0)
    b = fakes("B", load=100.0)
    router = _router(a, b)
    router.poll_once()
    recs = list(router.generate([1, 2, 3], max_new_tokens=3))
    toks = [r["token"] for r in recs if "token" in r]
    assert toks == [100, 101, 102]  # B served the full generation
    assert recs[-1].get("done")
    assert router.status()["retries"].get("stream_restart", 0) == 1
    router.stop()


def test_stream_broken_after_tokens_is_typed_not_retried(fakes):
    a = fakes("A", die_after_tokens=2)
    b = fakes("B")
    router = _router(a, b)
    router.poll_once()
    # force the pick onto A by loading B
    b.cfg["load"] = 100.0
    router.poll_once()
    got = []
    with pytest.raises(StreamBrokenError) as ei:
        for rec in router.generate([1, 2], max_new_tokens=6):
            if "token" in rec:
                got.append(rec["token"])
    assert got == [100, 101]
    assert ei.value.tokens_delivered == 2
    # B never saw a resubmit: splicing generations is the client's call
    assert not any(h == "/v1/generate" for h in b.hits)
    router.stop()


def test_stream_busy_replica_fails_over(fakes):
    a = fakes("A", generate="busy", load=0.0)
    b = fakes("B", load=100.0)
    router = _router(a, b)
    router.poll_once()
    toks = [r["token"] for r in router.generate([1], max_new_tokens=2)
            if "token" in r]
    assert toks == [100, 101]
    assert router.status()["retries"].get("busy", 0) == 1
    router.stop()


# ---------------------------------------------------------------------------
# CircuitBreaker under router concurrency (ISSUE 14 satellite)
# ---------------------------------------------------------------------------


def test_half_open_admits_exactly_one_probe_across_threads():
    """32 router worker threads hammer allow() the instant the cooldown
    expires: exactly one wins the half-open probe slot."""
    clk = [0.0]
    br = CircuitBreaker(failure_threshold=1, reset_timeout_s=1.0,
                        clock=lambda: clk[0])
    assert br.allow()
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert not br.allow()  # cooling down
    clk[0] = 2.0           # cooldown over
    admitted = []
    start = threading.Barrier(32)

    def hammer():
        start.wait()
        if br.allow():
            admitted.append(threading.get_ident())

    ts = [threading.Thread(target=hammer) for _ in range(32)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(admitted) == 1
    assert br.state == CircuitBreaker.HALF_OPEN
    # while the probe is out, nobody else gets in
    assert not br.allow()
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED


def test_probe_thread_dying_mid_call_releases_slot():
    """The router's contract: every admitted call reports an outcome
    even when the attempt dies on a non-wire exception — otherwise the
    half-open slot leaks and the endpoint is dead forever."""
    clk = [0.0]
    br = CircuitBreaker(failure_threshold=1, reset_timeout_s=1.0,
                        clock=lambda: clk[0])
    br.allow()
    br.record_failure()
    clk[0] = 2.0
    assert br.allow()  # the probe admission
    # the probe thread dies mid-call; the router's except-BaseException
    # arm reports the failure, releasing the slot into a fresh cooldown
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    clk[0] = 4.0
    assert br.allow()  # a NEW probe is admitted — the slot did not leak


def test_router_reports_failure_on_unexpected_exception(fakes, monkeypatch):
    """Router-level version of the slot-release test: _post dying on a
    MemoryError still notifies the breaker."""
    a = fakes("A")
    router = _router(a, retries=0)
    router.poll_once()

    def bomb(endpoint, path, payload, timeout):
        raise MemoryError("probe thread dies mid-call")

    monkeypatch.setattr(Router, "_post", staticmethod(bomb))
    rep = router._replicas[a.endpoint]
    before = rep.breaker.state
    with pytest.raises(MemoryError):
        router.predict({"x": [1]})
    assert before == CircuitBreaker.CLOSED
    # the failure was recorded (consecutive-failure count advanced), so
    # a wedged half-open can never happen through this path
    assert rep.breaker._failures == 1 or \
        rep.breaker.state != CircuitBreaker.CLOSED
    assert rep.inflight == 0  # local in-flight delta released too
    router.stop()


def test_breaker_opens_on_hammering_and_probe_recovers(fakes):
    a = fakes("A", predict="boom")
    b = fakes("B")
    router = _router(a, b, breaker_threshold=3, breaker_reset_s=0.5)
    router.poll_once()
    for _ in range(6):
        router.predict({"x": [1]})
    rep = router._replicas[a.endpoint]
    assert rep.breaker.state == CircuitBreaker.OPEN
    hits_before = len(a.hits)
    # while open, picks fail fast past A without touching it
    router.predict({"x": [1]})
    assert len(a.hits) == hits_before
    # A heals; after the cooldown one probe readmits it
    a.cfg["predict"] = "ok"
    time.sleep(0.6)
    tags = {router.predict({"x": [1]})["outputs"]["y"][0]
            for _ in range(10)}
    assert "A" in tags
    assert rep.breaker.state == CircuitBreaker.CLOSED
    transitions = [e for e in oe.recent(400, kind="fleet")
                   if e.get("action") == "breaker"
                   and e.get("endpoint") == a.endpoint]
    assert any(e["new"] == "open" for e in transitions)
    assert any(e["new"] == "closed" for e in transitions)
    router.stop()


# ---------------------------------------------------------------------------
# Rendezvous-backed membership
# ---------------------------------------------------------------------------


def test_rendezvous_membership_join_and_leave(fakes, tmp_path):
    from paddle_tpu.distributed.rendezvous import FileRendezvous

    a = fakes("A")
    b = fakes("B")
    root = str(tmp_path / "rdzv")
    ma = FileRendezvous(root, worker_id=a.endpoint, min_workers=1)
    mb = FileRendezvous(root, worker_id=b.endpoint, min_workers=1)
    ma.register()
    router = Router(rdzv_dir=root, poll_interval_s=0.05)
    router.poll_once()
    assert router.endpoints() == [a.endpoint]
    mb.register()  # scale-out: the next poll folds the joiner in
    router.poll_once()
    assert router.endpoints() == sorted([a.endpoint, b.endpoint])
    assert router.predict({"x": [1]})["outputs"]["y"][0] in ("A", "B")
    ma.leave()     # scale-in: leave() withdraws the member file
    router.poll_once()
    assert router.endpoints() == [b.endpoint]
    leaves = [e for e in oe.recent(200, kind="fleet")
              if e.get("action") == "member_leave"
              and e.get("endpoint") == a.endpoint]
    assert leaves
    router.stop()


# ---------------------------------------------------------------------------
# RouterServer HTTP front
# ---------------------------------------------------------------------------


def test_router_server_proxies_predict_and_status(fakes):
    a = fakes("A")
    router = _router(a)
    front = RouterServer(router)
    port = front.start(0)
    try:
        router.poll_once()
        body = json.dumps({"feeds": {"x": [1]}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            out = json.loads(r.read())
        assert out["outputs"]["y"] == ["A"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/status", timeout=10) as r:
            st = json.loads(r.read())
        assert st["fleet"] and st["world_size"] == 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        front.stop()


def test_router_server_healthz_503_when_no_replicas():
    router = Router([])
    front = RouterServer(router)
    port = front.start(0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/healthz", timeout=10)
        assert ei.value.code == 503
        body = json.dumps({"feeds": {"x": [1]}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/predict", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After")
    finally:
        front.stop()


def test_router_server_generate_malformed_input_is_400(fakes):
    """Non-numeric ids/max_new_tokens/timeout_s must come back as a
    400 JSON reply, never a dead handler thread dropping the
    connection (review regression)."""
    a = fakes("A")
    router = _router(a)
    front = RouterServer(router)
    port = front.start(0)
    try:
        router.poll_once()
        for payload in ({"ids": ["abc"]},
                        {"ids": [1], "max_new_tokens": "x"},
                        {"ids": [1], "timeout_s": "soon"},
                        {"ids": [1], "timeout_s": "soon",
                         "stream": False}):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400, payload
        assert not any(h == "/v1/generate" for h in a.hits)
    finally:
        front.stop()


def test_generate_replica_400_no_retry_no_ejection(fakes):
    """A replica's deterministic 400 on a generate submit is the
    CLIENT's error: no failover sweep, no breaker penalty, no health
    ejection (review regression — this previously ejected every
    healthy replica on a bad request)."""
    a = fakes("A", generate="bad_request", load=0.0)
    b = fakes("B", generate="bad_request", load=1.0)
    a.cfg["generate"] = "bad_request"
    router = _router(a, b)
    router.poll_once()

    # make the fakes answer generate with 400
    def patch(rep):
        rep.cfg["generate"] = "bad_request"

    patch(a), patch(b)
    with pytest.raises(ValueError):
        list(router.generate([1], max_new_tokens=2))
    # exactly one replica was asked; both stay healthy, breakers closed
    assert len(a.hits) + len(b.hits) == 1
    assert len(router.healthy_endpoints()) == 2
    st = router.status()
    assert all(r["breaker"] == "closed" for r in st["replicas"])
    router.stop()


def test_supervisor_endpoint_matches_spec_host(tmp_path):
    """_Slot endpoints must use ReplicaSpec.host — the string the
    replica registers in the rendezvous and the router routes to —
    or scale_in(endpoint=...) can never match (review regression)."""
    from paddle_tpu.distributed.launch_serve import (ReplicaSpec,
                                                     ReplicaSupervisor,
                                                     _Slot)

    spec = ReplicaSpec("unused_model_dir", host="10.1.2.3")
    sup = ReplicaSupervisor(spec, str(tmp_path / "rdzv"), replicas=0)
    # no start(): only the endpoint bookkeeping is under test
    slot = _Slot(0, 1234, host=getattr(sup.spec, "host", "127.0.0.1"))
    assert slot.endpoint == "10.1.2.3:1234"
    cmd = spec.command(0, 1234, "")
    assert cmd[:1] == [sys.executable] and "--host" in cmd
    assert cmd[cmd.index("--host") + 1] == "10.1.2.3"


def test_router_server_streams_generation(fakes):
    a = fakes("A")
    router = _router(a)
    front = RouterServer(router)
    port = front.start(0)
    try:
        router.poll_once()
        body = json.dumps({"ids": [1, 2], "max_new_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        toks, done = [], None
        with urllib.request.urlopen(req, timeout=10) as r:
            for line in r:
                rec = json.loads(line)
                if "token" in rec:
                    toks.append(rec["token"])
                elif rec.get("done"):
                    done = rec
        assert toks == [100, 101, 102]
        assert done and done["finish_reason"] == "length"
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# Autoscaler control law (hysteresis, cooldowns, bounds)
# ---------------------------------------------------------------------------


class _FakeRouterGauges:
    def __init__(self):
        self.load = 0.0
        self.p99 = None

    def mean_load_per_healthy(self):
        return self.load

    def recent_p99(self, window_s=30.0):
        return self.p99


class _FakeSupervisor:
    def __init__(self, n=1):
        self.n = n
        self.log = []

    def replica_count(self):
        return self.n

    def scale_out(self):
        self.n += 1
        self.log.append("out")
        return f"ep{self.n}"

    def scale_in(self, endpoint=None):
        self.n -= 1
        self.log.append("in")
        return f"ep{self.n + 1}"


def _scaler(router, sup, **kw):
    clk = kw.pop("clk")
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("high_load", 4.0)
    kw.setdefault("low_load", 0.5)
    kw.setdefault("breach_polls", 3)
    kw.setdefault("clear_polls", 4)
    kw.setdefault("out_cooldown_s", 5.0)
    kw.setdefault("in_cooldown_s", 8.0)
    return Autoscaler(router, sup, clock=lambda: clk[0], **kw)


def test_autoscaler_hysteresis_ignores_single_spike():
    clk = [0.0]
    router, sup = _FakeRouterGauges(), _FakeSupervisor(1)
    sc = _scaler(router, sup, clk=clk)
    router.load = 50.0
    assert sc.tick() is None and sc.tick() is None  # streak 2 < 3
    router.load = 0.6                               # spike clears
    assert sc.tick() is None
    router.load = 50.0                              # streak restarts
    assert sc.tick() is None and sc.tick() is None
    assert sup.n == 1


def test_autoscaler_scales_out_on_sustained_breach_and_cooldown():
    clk = [0.0]
    router, sup = _FakeRouterGauges(), _FakeSupervisor(1)
    sc = _scaler(router, sup, clk=clk)
    router.load = 50.0
    assert [sc.tick() for _ in range(3)] == [None, None, "out"]
    assert sup.n == 2
    # cooldown gates the next action even under continuous breach
    for _ in range(10):
        assert sc.tick() is None
    clk[0] = 6.0
    # the breach persisted through the whole cooldown (streak intact):
    # the first post-cooldown tick acts immediately
    assert sc.tick() == "out"
    assert sup.n == 3
    # bounded by max_replicas
    clk[0] = 20.0
    for _ in range(10):
        assert sc.tick() is None
    assert sup.n == 3


def test_autoscaler_scale_in_slower_and_floored():
    clk = [0.0]
    router, sup = _FakeRouterGauges(), _FakeSupervisor(3)
    sc = _scaler(router, sup, clk=clk)
    router.load = 0.1
    assert [sc.tick() for _ in range(4)] == [None, None, None, "in"]
    assert sup.n == 2
    clk[0] = 10.0
    for _ in range(4):
        sc.tick()
    assert sup.n == 1
    clk[0] = 30.0
    for _ in range(10):
        assert sc.tick() is None  # min_replicas floor
    assert sup.n == 1


def test_autoscaler_p99_signal_and_empty_fleet_hold():
    clk = [0.0]
    router, sup = _FakeRouterGauges(), _FakeSupervisor(1)
    sc = _scaler(router, sup, clk=clk, p99_high_ms=100.0)
    router.load = 1.0           # inside the hysteresis band
    router.p99 = 0.5            # 500ms > 100ms bound
    assert [sc.tick() for _ in range(3)] == [None, None, "out"]
    assert sup.n == 2
    # no healthy replica -> hold position, never "scale in to zero"
    router.load = None
    clk[0] = 100.0
    for _ in range(10):
        assert sc.tick() is None
    assert sup.n == 2


def test_autoscaler_rejects_inverted_band():
    with pytest.raises(ValueError):
        Autoscaler(_FakeRouterGauges(), _FakeSupervisor(),
                   high_load=1.0, low_load=2.0)


# ---------------------------------------------------------------------------
# Replica supervisor: crash respawn with capped backoff
# ---------------------------------------------------------------------------


class _CrashSpec:
    """ReplicaSpec stand-in whose 'replica' just exits rc."""

    def __init__(self, rc):
        self.rc = rc

    def command(self, slot_id, port, rdzv_dir):
        return [sys.executable, "-c",
                f"import sys; sys.exit({self.rc})"]


def test_supervisor_respawns_crash_until_budget(tmp_path):
    from paddle_tpu.distributed.launch_serve import ReplicaSupervisor

    sup = ReplicaSupervisor(_CrashSpec(1), str(tmp_path / "rdzv"),
                            replicas=1, max_respawns=2,
                            backoff_s=0.01)
    sup.start()
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            info = sup.slot_info()[0]
            if info["retired"] and info["respawns"] == 2:
                break
            time.sleep(0.05)
        info = sup.slot_info()[0]
        assert info["retired"] and not info["alive"]
        assert info["respawns"] == 2 and info["launches"] == 3
        exhausted = [e for e in oe.recent(200, kind="fleet")
                     if e.get("action") == "respawn_exhausted"]
        assert exhausted
    finally:
        sup.stop()


def test_supervisor_rc0_is_deliberate_not_respawned(tmp_path):
    from paddle_tpu.distributed.launch_serve import ReplicaSupervisor

    sup = ReplicaSupervisor(_CrashSpec(0), str(tmp_path / "rdzv"),
                            replicas=1, max_respawns=3,
                            backoff_s=0.01)
    sup.start()
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            info = sup.slot_info()[0]
            if info["retired"]:
                break
            time.sleep(0.05)
        info = sup.slot_info()[0]
        assert info["retired"] and info["respawns"] == 0 \
            and info["launches"] == 1
    finally:
        sup.stop()


class _IdleSpec:
    """ReplicaSpec stand-in whose 'replica' idles until signalled."""

    def command(self, slot_id, port, rdzv_dir):
        return [sys.executable, "-c", "import time; time.sleep(600)"]


def test_supervisor_scale_out_then_scale_in_retires_newest(tmp_path):
    """scale_out adds a live slot; scale_in signals the NEWEST live
    slot, which is retired for good (a deliberate exit is not a crash:
    no respawn) while the older slot keeps running."""
    from paddle_tpu.distributed.launch_serve import ReplicaSupervisor

    sup = ReplicaSupervisor(_IdleSpec(), str(tmp_path / "rdzv"),
                            replicas=1, max_respawns=2,
                            backoff_s=0.01)
    sup.start()
    try:
        first = sup.endpoints()
        added = sup.scale_out()
        assert sorted(sup.endpoints()) == sorted(first + [added])
        assert sup.scale_in() == added
        deadline = time.time() + 30
        while time.time() < deadline and sup.slot_info()[1]["alive"]:
            time.sleep(0.05)
        time.sleep(0.2)              # several monitor polls past it
        old, new = sup.slot_info()
        assert old["alive"] and not old["retired"]
        assert new["retired"] and not new["alive"]
        assert new["respawns"] == 0 and new["launches"] == 1
        assert sup.endpoints() == first
        acts = [e.get("action") for e in oe.recent(200, kind="fleet")]
        assert "scale_out" in acts and "scale_in" in acts
    finally:
        sup.stop(grace_s=5)
