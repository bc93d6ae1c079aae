"""JSON-over-HTTP serving frontend + the `Server` that ties the
subsystem together (engine + batcher + HTTP, one object to start/stop).

Routes (schema documented in SERVING.md §HTTP API):

  POST /v1/predict   {"feeds": {name: nested-list}, "timeout_s": opt}
                     → 200 {"outputs": {name: nested-list}, "batch": n}
                     → 400 malformed request / bad shapes
                     → 503 queue full or draining (admission control —
                       the client should back off or retry elsewhere)
                     → 504 request missed its deadline
                     → 500 engine error
  POST /v1/generate  {"ids": [tok,...], "max_new_tokens": N,
                      "stream": true|false, "timeout_s": opt}
                     token generation on the continuous-batching decode
                     engine (SERVING.md §Continuous batching). With
                     stream=true (default): a chunked
                     application/x-ndjson body, one {"token": t} line
                     per generated token as the scheduler emits it,
                     closed by {"done": true, "finish_reason": ...,
                     "tokens": n, "ttft_ms": x}. With stream=false: one
                     JSON reply carrying the full token list. 503 when
                     the decode queue is full, 404 when the server has
                     no decode engine attached.
  GET  /v1/status    queue depth, buckets, request/batch counters,
                     decode queue/slot-occupancy/TTFT block, uptime —
                     the operator's one-look view
  GET  /v1/load      the router's cheap load probe (SERVING.md §Fleet):
                     {"load": scalar, "inflight": n, "queue_depth": q,
                     "state": ...} touching only the batcher/decode
                     counters — power-of-two-choices picks must not pay
                     a full status() walk per poll
  GET  /v1/healthz   readiness, with a real serving-state signal for
                     the fleet router's health ejection: 200 only while
                     state == "serving"; 503 with {"state": "warming"}
                     before every bucket/phase is warmed, {"state":
                     "draining"} after drain() began (scale-in), and
                     {"state": "stopped"} once the decode engine or
                     batcher is gone. (The process-wide anomaly-aware
                     probe stays on the observability server,
                     PADDLE_TPU_METRICS_PORT.)
  GET  /v1/models    the multi-model surface (SERVING.md
                     §Multi-tenancy): one row per model slot — id,
                     program digest, adopted registry version, warm
                     state, per-slot request counts.

Multi-tenancy (SERVING.md §Multi-tenancy): /v1/predict and
/v1/generate accept optional "model" and "tenant" payload fields. A
`Server` holds one engine+batcher slot per model id (all sharing the
process and its HBM budget); QoS shed/quota rejections answer 503 with
a Retry-After header and the typed body {"shed": "<tier>", "kind":
"queue"|"quota"} that the fleet router classifies as an answer rather
than a retryable failure. `hot_swap()` (and the registry watcher
behind `attach_registry()`) replaces a slot's engine with one built
from a newly published artifact while the old batcher drains — zero
failed requests, and zero fresh compiles when the artifact's
executables are adopted.

Built on `observability.httpbase` — same silent logging, locked
idempotent start/stop, daemon threading, and atexit discipline as the
/metrics endpoint. Feed dtypes need not be declared client-side: the
Predictor casts to the model's declared feed dtypes, so plain JSON
numbers round-trip.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, Optional
from urllib.parse import urlparse

import numpy as np

from ..observability import events as _events
from ..observability import httpbase as _base
from ..observability import memwatch as _memwatch
from ..observability import slo as _slo
from ..observability import timeseries as _timeseries
from ..observability import tracing as _tracing
from ..observability import metrics as _m
from ..observability.metrics import _json_safe
from .decode import DecodeEngine
from .batcher import (Batcher, EngineError, QueueFullError,
                      RequestTimeout, ServerClosed)
from .engine import Engine, ServingConfig
from .qos import QoSPolicy, ShedError

__all__ = ["Server"]

MODEL_SWAPS = _m.counter(
    "paddle_tpu_model_swaps_total",
    "Completed zero-downtime model hot-swaps, by model id",
    labelnames=("model",))


class _ServingHandler(_base.QuietHandler):
    server_version = "paddle-tpu-serving"
    # chunked transfer (the /v1/generate stream) needs HTTP/1.1; all
    # non-chunked replies already send explicit Content-Length, which
    # 1.1 keep-alive requires
    protocol_version = "HTTP/1.1"
    serving: "Server" = None  # bound per-Server via a subclass

    _tctx = None  # per-request TraceContext, set at the top of do_*

    def _json_reply(self, code: int, payload: Dict, headers=None):
        # strict-JSON discipline (same as metrics.dump): a model output
        # containing NaN/Inf must not make json.dumps emit bare NaN
        # tokens that RFC-8259 clients reject — non-finite floats become
        # strings ("nan"/"inf"/"-inf"), documented in SERVING.md
        hdrs = dict(headers or {})
        # every /v1/* reply carries the request id + traceparent so the
        # caller (and the fleet router's logs) can join against the
        # trace sink and the JSONL event log (SERVING.md §HTTP API)
        hdrs.update(_tracing.response_headers(self._tctx))
        self._reply(code, "application/json",
                    json.dumps(_json_safe(payload)) + "\n",
                    extra_headers=hdrs)

    def do_GET(self):  # noqa: N802 - stdlib naming
        try:
            self._tctx = _tracing.begin_request(self.headers)
            path = urlparse(self.path).path
            if path == "/v1/status":
                self._json_reply(200, self.serving.status())
            elif path == "/v1/load":
                self._json_reply(200, self.serving.load())
            elif path == "/v1/healthz":
                state = self.serving.state()
                self._json_reply(
                    200 if state == "serving" else 503,
                    {"status": "ok" if state == "serving"
                     else "unavailable", "state": state})
            elif path == "/v1/models":
                self._json_reply(200, {"models": self.serving.models()})
            else:
                self._reply(404, "text/plain",
                            "not found; routes: POST /v1/predict, "
                            "GET /v1/status /v1/load /v1/healthz "
                            "/v1/models\n")
        except _base.CLIENT_GONE:
            pass

    # -- token streaming (/v1/generate) --------------------------------

    def _chunk(self, line: str):
        # ONE write a chunk: `wfile` is unbuffered, so the size line, the
        # data and the CRLF as three writes were three `sendall` calls a
        # token a stream, each giving up the interpreter lock and asking
        # for it back; with 64 streams that contention was most of the
        # decode loop's turn (PERF.md section 6, PR 34)
        data = line.encode("utf-8")
        self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
        self.wfile.flush()

    def _shed_reply(self, e: ShedError):
        """The typed shed/quota 503: Retry-After + {"shed": tier} body
        the fleet router classifies as an ANSWER (no failover retry) —
        re-sending a deliberately shed request onto a surviving replica
        amplifies exactly the overload the shed is relieving."""
        self._json_reply(
            503, {"error": str(e), "shed": e.tier, "kind": e.kind,
                  "tenant": e.tenant,
                  "retry_after_s": e.retry_after_s},
            headers={"Retry-After":
                     str(max(1, int(round(e.retry_after_s))))})

    def _do_generate(self, payload: Dict):
        from .batcher import QueueFullError, ServerClosed

        model = payload.get("model")
        decode = self.serving._decode_for(model)
        if decode is None:
            if model is not None \
                    and str(model) not in self.serving._decodes:
                self._json_reply(404, {"error": f"unknown model "
                                                f"{str(model)!r}"})
                return
            self._json_reply(404, {"error": "no decode engine attached "
                                            "to this server"})
            return
        # the request-root span, handler entry to last byte written:
        # decode.submit below captures it (and, sampled, its child
        # context), so the queue-wait/prefill/TTFT spans recorded later
        # by the scheduler thread land under this request
        sp = None
        if _tracing.recording or self._tctx.sampled:
            sp = _tracing.open_span("http.generate", "serve",
                                    ctx=self._tctx)
        try:
            self._generate_traced(payload, decode, sp)
        finally:
            if sp is not None:
                sp.close(trace_id=self._tctx.trace_id)

    def _generate_traced(self, payload: Dict, decode, sp=None):
        ids = payload.get("ids")
        if not isinstance(ids, (list, tuple)) or not ids:
            self._json_reply(400, {"error": 'missing/empty "ids" list'})
            return
        max_new = payload.get("max_new_tokens", 16)
        stream = bool(payload.get("stream", True))
        timeout = payload.get("timeout_s")
        try:
            handle = decode.submit(ids, max_new_tokens=int(max_new),
                                   tenant=payload.get("tenant"))
        except ShedError as e:
            self._shed_reply(e)
            return
        except (QueueFullError, ServerClosed) as e:
            self._json_reply(503, {"error": str(e)},
                             headers=self.serving._retry_after())
            return
        except (ValueError, TypeError) as e:
            self._json_reply(400, {"error": str(e)})
            return
        if sp is not None:
            sp.rid = handle.rid
        if not stream:
            try:
                toks = handle.result(timeout_s=timeout)
            except Exception as e:
                # the reply is an error, so nobody will ever read the
                # rest of this generation — free its slot/blocks now
                decode.cancel(handle)
                self._json_reply(500, {"error": f"{type(e).__name__}: "
                                                f"{e}"})
                return
            info = handle.info
            self._json_reply(200, {
                "tokens": toks, "finish_reason": info["finish_reason"],
                "ttft_ms": round(info["ttft_s"] * 1000, 3)
                if info["ttft_s"] is not None else None})
            return
        # streaming: chunked ndjson, one line per token as it lands
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("Cache-Control", "no-cache")
        for name, value in _tracing.response_headers(self._tctx).items():
            self.send_header(name, value)
        self.end_headers()
        n = 0
        try:
            for tok in handle.tokens(timeout_s=timeout):
                self._chunk(json.dumps({"token": int(tok)}) + "\n")
                if n == 0 and _tracing.recording:
                    # the engine's first token to its bytes on the socket
                    _tracing.record(
                        "http.first_write", handle.t_first,
                        _tracing.clock(), "serve", parent=sp,
                        rid=handle.rid)
                n += 1
            info = handle.info
            self._chunk(json.dumps(_json_safe({
                "done": True, "tokens": n,
                "finish_reason": info["finish_reason"],
                "ttft_ms": round(info["ttft_s"] * 1000, 3)
                if info["ttft_s"] is not None else None})) + "\n")
        except _base.CLIENT_GONE:
            # the reader hung up mid-stream: abandon the generation so
            # its decode slot and KV blocks free NOW instead of after
            # max_new_tokens of unread work
            decode.cancel(handle)
            return
        except Exception as e:
            decode.cancel(handle)
            # headers are gone; the error must travel in-band
            try:
                self._chunk(json.dumps({
                    "done": True, "error": f"{type(e).__name__}: {e}",
                    "tokens": n}) + "\n")
            except _base.CLIENT_GONE:
                return
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()
        # one generation per connection: chunked keep-alive reuse buys
        # nothing here and a half-read stream must not poison the next
        # request on the socket
        self.close_connection = True

    def do_POST(self):  # noqa: N802 - stdlib naming
        try:
            # extract-or-start the request's trace context (W3C
            # traceparent in, X-Request-Id/traceparent out); the active
            # span threads through batcher/decode/engine spans
            self._tctx = _tracing.begin_request(self.headers)
            path = urlparse(self.path).path
            if path == "/v1/profile":
                # on-demand capture on the SERVING port: the fleet
                # router can profile a replica under live traffic
                # through the same address it routes inference to.
                # This handler thread blocks for the window; the
                # ThreadingHTTPServer keeps /v1/predict flowing.
                from ..observability.httpd import handle_profile_request

                code, body = handle_profile_request(self)
                self._reply(code, "application/json", body)
                return
            if path not in ("/v1/predict", "/v1/generate"):
                self._reply(404, "text/plain",
                            "not found; POST routes: /v1/predict, "
                            "/v1/generate, /v1/profile\n")
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                payload = json.loads(self.rfile.read(length))
            except (ValueError, TypeError):
                self._json_reply(400, {"error": "body must be JSON"})
                return
            if path == "/v1/generate":
                if not isinstance(payload, dict):
                    self._json_reply(400, {"error": "body must be a "
                                                    "JSON object"})
                    return
                self._do_generate(payload)
                return
            with _tracing.trace_span("http.predict", cat="serve",
                                     ctx=self._tctx):
                self._do_predict(payload)
        except _base.CLIENT_GONE:
            pass

    def _do_predict(self, payload):
        try:
            feeds = payload.get("feeds") if isinstance(payload, dict) \
                else None
            if not isinstance(feeds, dict) or not feeds:
                self._json_reply(400, {"error":
                                       'missing/empty "feeds" object'})
                return
            try:
                arrays = {str(k): np.asarray(v) for k, v in feeds.items()}
            except (ValueError, TypeError):
                self._json_reply(400, {"error": "feeds must be rectangular "
                                               "numeric arrays"})
                return
            timeout = payload.get("timeout_s")
            model = payload.get("model")
            if model is not None \
                    and str(model) not in self.serving._model_ids():
                self._json_reply(404, {"error": f"unknown model "
                                                f"{str(model)!r}"})
                return
            try:
                outs = self.serving.submit(
                    arrays, timeout_s=timeout, model=model,
                    tenant=payload.get("tenant"))
            except ShedError as e:
                self._shed_reply(e)
                return
            except (QueueFullError, ServerClosed) as e:
                # draining replicas add Retry-After so the fleet router
                # (and any well-behaved client) re-sends elsewhere NOW
                # and re-polls this replica after the drain window
                self._json_reply(503, {"error": str(e)},
                                 headers=self.serving._retry_after())
                return
            except RequestTimeout as e:
                self._json_reply(504, {"error": str(e)})
                return
            except EngineError as e:
                # model/engine failure is the server's fault — a 400
                # would make clients retry a request that cannot succeed
                self._json_reply(500, {"error": str(e)})
                return
            except ValueError as e:
                # pre-enqueue validation (empty/ragged/oversize feeds)
                self._json_reply(400, {"error": str(e)})
                return
            except Exception as e:
                self._json_reply(500, {"error": f"{type(e).__name__}: {e}"})
                return
            batch = next(iter(arrays.values())).shape[0] \
                if next(iter(arrays.values())).ndim else 1
            self._json_reply(200, {
                "outputs": {k: np.asarray(v).tolist()
                            for k, v in outs.items()},
                "batch": int(batch)})
        except _base.CLIENT_GONE:
            pass


class Server:
    """The dynamic-batching TPU inference server: build with a
    ServingConfig (or hand in an existing Predictor), `start()` to warm
    the buckets and begin listening, `stop()` to drain and shut down.
    Both are idempotent; stop is also registered atexit so tests and
    crashing deployments never leak the listener or batcher thread."""

    def __init__(self, config: ServingConfig,
                 predictor=None, decode=None, models=None,
                 registry=None):
        """`decode`, when given, is a `decode.DecodeEngine` (or a dict
        `{model_id: DecodeEngine}` for multi-model generation); the
        server then also answers POST /v1/generate and folds the decode
        block into /v1/status. A decode-only server (no model_dir, no
        predictor) skips the predict engine entirely — /v1/predict
        answers 503. `models`, when given, is `{model_id:
        ServingConfig}` for ADDITIONAL predict models served from this
        process alongside `config`'s (the default slot, named by
        `config.model_id`); all slots share the process, its HBM
        budget, and one listener. `registry`, when given, is a
        `registry.ModelRegistry` the server watches for hot-swaps
        (see attach_registry)."""
        self.config = config
        self._default_id = getattr(config, "model_id", "default")
        decodes = decode if isinstance(decode, dict) else \
            ({self._default_id: decode} if decode is not None else {})
        self._decodes: Dict[str, DecodeEngine] = \
            {str(k): v for k, v in decodes.items()}
        # annotated so tools/lockgraph.py can type the attribute (the
        # value is a constructor parameter it cannot infer from)
        self._decode: Optional[DecodeEngine] = \
            self._decodes.get(self._default_id)
        self._engine = None \
            if (self._decodes and config.model_dir is None
                and predictor is None) \
            else Engine(config, predictor=predictor)
        self._batcher: Optional[Batcher] = None
        # additional predict-model slots: model_id -> {config, engine,
        # batcher}; engines build NOW (fail a bad config at
        # construction like the default slot), batchers at start()
        self._extra: Dict[str, Dict] = {}
        for mid, mcfg in (models or {}).items():
            mid = str(mid)
            if mid == self._default_id:
                raise ValueError(
                    f"models= duplicates the default slot {mid!r}")
            self._extra[mid] = {"config": mcfg,
                                "engine": Engine(mcfg),
                                "batcher": None}
        self._qos = QoSPolicy.from_spec(getattr(config, "qos", None))
        handler = type("_BoundServingHandler", (_ServingHandler,),
                       {"serving": self})
        self._http = _base.HTTPServerHandle(
            handler, thread_name="paddle-tpu-serving-http")
        # deferred import: the analysis package must not load during
        # package bootstrap; constructors only run after it
        from ..analysis import lockcheck as _lockcheck

        self._lock = _lockcheck.Lock("serving.httpd.Server._lock")
        self._started_t: Optional[float] = None
        self._draining = False
        # registry hot-swap state: adopted version per model slot, the
        # watcher thread, and its stop flag
        self._versions: Dict[str, int] = {}
        self._registry = None
        self._watch_ids = None
        self._watch_poll_s = 1.0
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None
        if registry is not None:
            self.attach_registry(registry)

    # -- lifecycle -----------------------------------------------------

    def start(self, port: Optional[int] = None) -> int:
        """Warm the buckets, start the batcher and the HTTP listener.
        Returns the bound port; a second call returns it unchanged."""
        with self._lock:
            if self._started_t is not None:
                return self._http.port()
            with _tracing.boot_span("boot.server_start"):
                return self._start_locked(port)

    def _start_locked(self, port: Optional[int]) -> int:
        self._draining = False
        # thread-spawn ordering is the leak discipline: everything
        # that can FAIL (warmups, the bind) happens before anything
        # that starts a thread, except the batcher — whose
        # constructor spawns — which is therefore created last
        # before the bind and stopped if the bind raises. The
        # decode scheduler starts only after the bind succeeds, so
        # a failed start never leaves it running (and never kills
        # the caller's engine, whose stop() is terminal).
        if self.config.warmup:
            for dec in self._decodes.values():
                if not dec.warmed:
                    dec.warmup()
        batcher = None
        if self._engine is not None:
            if self.config.warmup:
                self._engine.warmup()
            batcher = self._make_batcher(self._engine, self.config)
        extra_batchers = []
        try:
            for mid, slot in self._extra.items():
                if slot["config"].warmup:
                    slot["engine"].warmup()
                extra_batchers.append(
                    (mid, self._make_batcher(slot["engine"],
                                             slot["config"])))
            bound = self._http.start(
                self.config.port if port is None else port,
                host=self.config.host)
        except BaseException:
            if batcher is not None:
                batcher.stop()  # failed bind must not leak the thread
            for _, b in extra_batchers:
                b.stop()
            raise
        for dec in self._decodes.values():
            dec.start()
        self._batcher = batcher
        for mid, b in extra_batchers:
            self._extra[mid]["batcher"] = b
        self._started_t = time.monotonic()
        import atexit

        atexit.register(self.stop)
        # telemetry pipeline: the env-gated TS recorder plus the
        # SLO evaluator when the config declares objectives (both
        # no-ops without PADDLE_TPU_TS_DIR)
        _timeseries.maybe_start_recorder()
        _slo.maybe_start_evaluator(
            spec_path=getattr(self.config, "slo_spec", None))
        _events.emit("serve_start", port=bound,
                     buckets=list(self._engine.policy.buckets)
                     if self._engine is not None else [],
                     decode=bool(self._decodes),
                     models=self._model_ids(),
                     qos=self._qos is not None,
                     max_queue=self.config.max_queue,
                     max_wait_ms=self.config.max_wait_ms)
        self._maybe_start_watcher()
        return bound

    def _make_batcher(self, engine: Engine, cfg: ServingConfig) -> Batcher:
        return Batcher(
            engine.run_batch, engine.policy,
            max_queue=cfg.max_queue,
            max_wait_ms=cfg.max_wait_ms,
            timeout_s=cfg.timeout_s,
            output_batched=engine.output_batched,
            qos=self._qos)

    def drain(self, timeout: float = 30.0):
        """Graceful drain, the fleet's scale-in half-step (SERVING.md
        §Fleet): the listener STAYS UP — so the router's health probe
        sees state "draining" (503) and in-flight streams finish — but
        new work is rejected with 503 + Retry-After, and this call
        blocks until pending predict batches and decode generations
        completed (or `timeout` passed). Call stop() afterwards to tear
        the listener down. Idempotent."""
        with self._lock:
            if self._draining or self._started_t is None:
                already = True
            else:
                self._draining = True
                already = False
            batchers = self._all_batchers()
            decodes = list(self._decodes.values())
        if not already:
            _events.emit("serve_drain",
                         queue_depth=sum(b.depth() for b in batchers))
        # ONE deadline across every engine: `timeout` bounds the whole
        # drain, not each stage (a supervisor sizing its SIGKILL grace
        # against drain_timeout_s must not be off by 2x)
        deadline = time.monotonic() + float(timeout)
        for batcher in batchers:
            # stop() is the drain: no new admissions, pending batches
            # finish, the thread joins
            batcher.stop(timeout=max(0.0, deadline - time.monotonic()))
        for decode in decodes:
            decode.drain(timeout_s=max(0.0,
                                       deadline - time.monotonic()))

    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def _retry_after(self) -> Optional[Dict[str, str]]:
        """Retry-After header for 503 replies while draining (predicts
        rejected mid-drain should be re-sent to another replica now and
        back here only after the drain completes)."""
        return {"Retry-After": "1"} if self.draining() else None

    def state(self) -> str:
        """One-word serving state for the health probe: "warming" until
        every bucket/phase is warm, "serving" while traffic flows,
        "draining" after drain() began, "stopped" before start / after
        stop / when the decode engine was stopped underneath us."""
        with self._lock:
            if self._started_t is None:
                return "stopped"
            if self._draining:
                return "draining"
            batchers = self._all_batchers()
            decodes = list(self._decodes.values())
            engines = self._all_engines()
        if any(d._closed for d in decodes):
            return "stopped"
        if any(b.draining() for b in batchers):
            return "draining"
        if self.config.warmup and (
                any(not e.warmed for e in engines)
                or any(not d.warmed for d in decodes)):
            return "warming"
        return "serving"

    def load(self) -> Dict:
        """The cheap load probe behind GET /v1/load: queue depth +
        in-flight work as one scalar, touching only counters (no bucket
        table, no KV stats — the router polls this per replica per
        interval)."""
        depth = sum(b.depth() for b in self._all_batchers())
        inflight = sum(b.inflight() for b in self._all_batchers())
        for decode in self._decodes.values():
            d_wait, d_active = decode.load()
            depth += d_wait
            inflight += d_active
        return {"load": float(depth + inflight), "inflight": inflight,
                "queue_depth": depth, "state": self.state(),
                "models": self._model_ids()}

    def stop(self):
        """Stop accepting (listener down first), drain the batcher so
        in-flight requests finish, then emit `serve_stop`. Idempotent;
        unregisters its atexit hook so stopped servers are collectable."""
        # the registry watcher joins OUTSIDE the lock: its poll loop
        # takes the lock for hot-swaps, so joining under it deadlocks
        self._watch_stop.set()
        watcher = self._watch_thread
        if watcher is not None and watcher.is_alive():
            watcher.join(timeout=10.0)
        self._watch_thread = None
        # the whole teardown runs under the lock so a concurrent start()
        # cannot interleave (and e.g. have its fresh batcher killed or
        # its "bound" port be the one being closed)
        with self._lock:
            started = self._started_t is not None
            self._started_t = None
            import atexit

            atexit.unregister(self.stop)
            self._http.stop()
            self._stop_slots_locked()
            if not started:
                return  # safety path: a start() that raised mid-way
            counts = self._counts()
        _events.emit("serve_stop", ok=counts["ok"],
                     rejected=counts["rejected"],
                     timeout=counts["timeout"])

    def _counts(self) -> Dict[str, int]:
        """THIS server's outcomes, summed over model slots (the
        Prometheus counter is process-global; batchers keep
        per-instance counts)."""
        out = {o: 0 for o in ("ok", "rejected", "timeout", "error")}
        for b in self._all_batchers():
            for k, v in b.outcome_counts().items():
                out[k] = out.get(k, 0) + v
        return out

    def _stop_slots_locked(self):
        """Stop every slot's batcher and decode engine (caller holds
        Server._lock). The typed default-slot references double as the
        lockgraph witness for the ledgered order: Server._lock wraps
        the inner component locks during teardown."""
        if self._batcher is not None:
            self._batcher.stop()
        if self._decode is not None:
            self._decode.stop()
        for batcher in self._all_batchers():
            if batcher is not self._batcher:
                batcher.stop()
        for decode in self._decodes.values():
            if decode is not self._decode:
                decode.stop()

    def port(self) -> Optional[int]:
        return self._http.port()

    # -- model slots (multi-model surface) -----------------------------

    def _all_batchers(self):
        out = [] if self._batcher is None else [self._batcher]
        out.extend(s["batcher"] for s in self._extra.values()
                   if s["batcher"] is not None)
        return out

    def _all_engines(self):
        out = [] if self._engine is None else [self._engine]
        out.extend(s["engine"] for s in self._extra.values())
        return out

    def _model_ids(self):
        ids = set(self._extra) | set(self._decodes)
        if self._engine is not None:
            ids.add(self._default_id)
        return sorted(ids)

    def _slot(self, model: Optional[str]):
        """(engine, batcher) for a model id; None model = the default
        slot. Raises KeyError for an unknown id."""
        mid = self._default_id if model is None else str(model)
        if mid == self._default_id and mid not in self._extra:
            # the default slot, possibly empty (decode-only server)
            return self._engine, self._batcher
        slot = self._extra[mid]
        return slot["engine"], slot["batcher"]

    def _decode_for(self, model: Optional[str]) -> Optional[DecodeEngine]:
        if model is None:
            return self._decode
        return self._decodes.get(str(model))

    def models(self) -> list:
        """The /v1/models rows: one per model slot (predict and/or
        decode), with the served program's digest, the adopted registry
        version, and warm state. Slot pointers are snapshotted under
        the server lock but read AFTER it: outcome_counts() takes the
        batcher condition, and holding Server._lock across another
        component's lock would widen the lock order for a status
        read."""
        slots = []
        with self._lock:
            for mid in self._model_ids():
                try:
                    eng, batcher = self._slot(mid)
                except KeyError:
                    eng, batcher = None, None
                slots.append((mid, self._versions.get(mid), eng,
                              batcher, self._decodes.get(mid)))
        rows = []
        for mid, version, eng, batcher, dec in slots:
            row = {"id": mid, "version": version,
                   "default": mid == self._default_id}
            if eng is not None:
                row.update(
                    kind="predict",
                    digest=eng._model_digest(),
                    warmed=eng.warmed,
                    warmstart_adopted=eng.warmstart_adopted,
                    buckets=[int(b) for b in eng.policy.buckets])
                if batcher is not None:
                    row["requests"] = batcher.outcome_counts()
            if dec is not None:
                row["decode"] = {
                    "warmed": dec.warmed,
                    "warmstart_adopted": dec.warmstart_adopted,
                    "digest": dec._model_digest()}
                row.setdefault("kind", "decode")
            rows.append(row)
        return rows

    # -- zero-downtime hot-swap ----------------------------------------

    def hot_swap(self, model_id: Optional[str] = None, *,
                 model_dir: Optional[str] = None,
                 warmstart: Optional[str] = None,
                 version: Optional[int] = None) -> Dict:
        """Replace one predict slot's engine with one built from a new
        artifact, without dropping traffic: the replacement engine
        builds and WARMS before the slot pointer moves (with an adopted
        warmstart this is deserialization, zero fresh compiles), new
        requests flow to it from the swap instant, and the old slot's
        batcher then drains so every in-flight request completes —
        zero failed requests. Returns the swap record (also emitted as
        a `model_swap` event)."""
        mid = self._default_id if model_id is None else str(model_id)
        if mid == self._default_id and self._engine is not None:
            old_cfg = self.config
        elif mid in self._extra:
            old_cfg = self._extra[mid]["config"]
        else:
            raise KeyError(f"unknown model slot {mid!r}; serving "
                           f"{self._model_ids()}")
        import copy

        new_cfg = copy.copy(old_cfg)
        if model_dir is not None:
            new_cfg.model_dir = model_dir
        new_cfg.warmstart = warmstart
        t0 = time.monotonic()
        # the expensive part happens OFF the serving path: the old
        # engine keeps answering while this one builds and warms
        new_engine = Engine(new_cfg)
        if new_cfg.warmup:
            new_engine.warmup()
        new_batcher = None
        with self._lock:
            started = self._started_t is not None
            if started:
                new_batcher = self._make_batcher(new_engine, new_cfg)
            if mid == self._default_id and self._engine is not None:
                old_batcher = self._batcher
                self.config = new_cfg
                self._engine = new_engine
                self._batcher = new_batcher
            else:
                slot = self._extra[mid]
                old_batcher = slot["batcher"]
                self._extra[mid] = {"config": new_cfg,
                                    "engine": new_engine,
                                    "batcher": new_batcher}
            if version is not None:
                self._versions[mid] = int(version)
        # drain the displaced batcher AFTER the pointer moved: its
        # in-flight and queued requests complete against the OLD engine
        # (their feeds were validated against its signature set) while
        # new arrivals already land on the new one
        if old_batcher is not None:
            old_batcher.stop()
        record = {
            "model": mid, "version": version,
            "digest": new_engine._model_digest(),
            "warmstart_adopted": new_engine.warmstart_adopted,
            "swap_s": round(time.monotonic() - t0, 3)}
        MODEL_SWAPS.inc(model=mid)
        if version is not None:
            from .registry import MODEL_VERSION

            MODEL_VERSION.set(int(version), model=mid)
        _events.emit("model_swap", **record)
        return record

    def attach_registry(self, registry, model_ids=None,
                        poll_s: float = 1.0):
        """Watch a `registry.ModelRegistry` and hot-swap slots as new
        versions publish. `model_ids` bounds the watch (default: this
        server's predict slots). The watcher starts with the server
        (or immediately if already started) and stops with it. A slot
        already serving the published program digest just records the
        version — no redundant swap."""
        self._registry = registry
        self._watch_ids = None if model_ids is None \
            else [str(m) for m in model_ids]
        self._watch_poll_s = float(poll_s)
        self._maybe_start_watcher()

    def _maybe_start_watcher(self):
        if self._registry is None or self._watch_thread is not None \
                or self._started_t is None:
            return
        self._watch_stop.clear()
        self._watch_thread = threading.Thread(
            target=self._watch_loop, name="paddle-tpu-registry-watch",
            daemon=True)
        self._watch_thread.start()

    def _watch_ids_now(self):
        if self._watch_ids is not None:
            return self._watch_ids
        ids = [] if self._engine is None else [self._default_id]
        ids.extend(self._extra)
        return ids

    def _watch_loop(self):
        while not self._watch_stop.wait(self._watch_poll_s):
            for mid in self._watch_ids_now():
                try:
                    self._adopt_if_new(mid)
                except Exception as e:
                    # a bad publish must not kill the watcher (the
                    # current engine keeps serving); surface it
                    _events.emit("model_swap_failed", model=mid,
                                 error=f"{type(e).__name__}: "
                                       f"{str(e)[:200]}")

    def _adopt_if_new(self, mid: str):
        reg = self._registry
        ver = reg.version(mid)
        if ver is None or ver <= self._versions.get(mid, 0):
            return
        entry = reg.resolve(mid)   # digest-verified blob
        try:
            eng, _ = self._slot(mid)
        except KeyError:
            eng = None
        if eng is not None and entry.get("model_digest") is not None \
                and entry["model_digest"] == eng._model_digest() \
                and eng.warmstart_adopted:
            # same program, already warm from an adopted artifact:
            # record the version, skip the redundant rebuild
            with self._lock:
                self._versions[mid] = ver
            return
        self.hot_swap(mid, model_dir=entry.get("model_dir"),
                      warmstart=entry["path"], version=ver)

    # -- request path --------------------------------------------------

    def submit(self, feeds: Dict[str, np.ndarray],
               timeout_s: Optional[float] = None,
               model: Optional[str] = None,
               tenant: Optional[str] = None) -> Dict[str, np.ndarray]:
        """In-process entry to the batched path (the HTTP handler and
        embedded deployments share it). `model` picks the slot (None =
        default); `tenant` flows to QoS admission."""
        try:
            engine, batcher = self._slot(model)
        except KeyError:
            raise ValueError(f"unknown model {str(model)!r}; serving "
                             f"{self._model_ids()}")
        if batcher is None:
            raise ServerClosed("server not started"
                               if engine is not None else
                               "no predict engine on this server "
                               "(decode-only deployment)")
        return batcher.submit(feeds, timeout_s=timeout_s, tenant=tenant)

    def status(self) -> Dict:
        up = None if self._started_t is None \
            else round(time.monotonic() - self._started_t, 3)
        batcher = self._batcher
        probe = self.load()
        st = {
            "uptime_s": up,
            "port": self._http.port(),
            "state": probe["state"],
            "load": probe["load"],
            "inflight": probe["inflight"],
            "queue_depth": batcher.depth() if batcher else 0,
            "max_queue": self.config.max_queue,
            "max_wait_ms": self.config.max_wait_ms,
            "timeout_s": self.config.timeout_s,
            "requests": self._counts(),
            "memory": _memwatch.status_block(),
            "models": probe["models"],
        }
        if self._qos is not None:
            st["qos"] = self._qos.spec_dict()
        if self._engine is not None:
            st.update(self._engine.status())
        if self._decode is not None:
            st["decode"] = self._decode.status()
        for mid, dec in self._decodes.items():
            if dec is not self._decode:
                st.setdefault("decodes", {})[mid] = dec.status()
        return st
