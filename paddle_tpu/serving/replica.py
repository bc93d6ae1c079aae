"""One fleet replica process: `python -m paddle_tpu.serving.replica`.

The unit the ReplicaSupervisor (distributed/launch_serve.py) spawns and
the Router (serving/router.py) discovers: boots a `serving.Server` —
from a PR 6 warmstart artifact when one is given, so a scale-out
replica is serving in seconds instead of paying an XLA warmup —
registers its endpoint as a PR 9 `FileRendezvous` member (worker_id IS
the "host:port" endpoint; the heartbeat thread keeps it live), and
serves until SIGTERM, which triggers the graceful scale-in sequence:

  1. leave the rendezvous (the router's next poll stops picking us),
  2. drain (listener stays up: in-flight work finishes, stragglers get
     503 + Retry-After and fail over through the router),
  3. stop, exit 0 (rc 0 tells the supervisor the exit was deliberate —
     anything else is a crash and respawns the slot).

Serving membership needs no generations/barrier — replicas never form a
collective — so this module uses only register/heartbeat/leave from the
rendezvous protocol; the router reads `live_members()`.

Stdout speaks one JSON "ready" line once serving (the supervisor
waits on it): {"ready": true, "endpoint": ..., "pid": ...,
"warmstart_adopted": n, "slot": k}.

Multi-tenant flags (SERVING.md §Multi-tenancy): `--model-id` names the
model this replica serves (advertised to the router through /v1/load),
`--qos FILE` loads a tier/tenant policy JSON enabling weighted-fair
admission, and `--registry DIR` watches a model registry so newly
published artifact versions are hot-swapped in without a restart.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def _build_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="paddle_tpu.serving.replica", description=__doc__)
    ap.add_argument("--model-dir", default="",
                    help="saved inference model for the predict path "
                    "(optional when --decode-tiny builds a decode-only "
                    "replica)")
    ap.add_argument("--decode-tiny", type=int, default=None,
                    metavar="SEED",
                    help="attach a tiny-GPT continuous-batching decode "
                    "engine initialized from this seed: a toy "
                    "token-serving replica (POST /v1/generate)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 binds an ephemeral port (printed in the "
                    "ready line and registered in the rendezvous)")
    ap.add_argument("--rdzv-dir", default="",
                    help="fleet membership store (PADDLE_TPU_RDZV_DIR "
                    "fallback); empty = standalone replica")
    ap.add_argument("--warmstart", default="",
                    help="PR 6 warmstart artifact: boot without paying "
                    "XLA compiles")
    ap.add_argument("--slot", type=int, default=-1,
                    help="supervisor slot id (informational)")
    ap.add_argument("--buckets", default="",
                    help="comma batch buckets (default: policy pow2)")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-queue", type=int, default=128)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--timeout-s", type=float, default=30.0)
    ap.add_argument("--precision", default="f32")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0)
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    ap.add_argument("--cpu", action="store_true",
                    help="pin jax to the CPU platform (fleet simulation "
                    "/ tests; a chip belongs to ONE process, so N "
                    "replicas on one chip is not a deployment)")
    ap.add_argument("--model-id", default="default",
                    help="model id this replica's default slot serves "
                    "(advertised in /v1/load for the router's "
                    "model-aware picks; SERVING.md §Multi-tenancy)")
    ap.add_argument("--qos", default="",
                    help="path to a QoS policy JSON file ({tiers, "
                    "default_tier, tenants}) enabling tiered "
                    "admission + weighted-fair scheduling")
    ap.add_argument("--registry", default="",
                    help="model registry root to watch: newly "
                    "published artifact versions are hot-swapped in "
                    "with zero downtime")
    ap.add_argument("--registry-poll-s", type=float, default=1.0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _build_args(argv)
    import jax

    if args.cpu:
        # the package import above already loaded jax, which reads
        # JAX_PLATFORMS only then: pin through the config, before any
        # backend initializes
        jax.config.update("jax_platforms", "cpu")
    from ..core.compile_cache import place_jax_cache

    place_jax_cache()

    from .engine import ServingConfig
    from .httpd import Server

    if not args.model_dir and args.decode_tiny is None:
        print(json.dumps({"ready": False,
                          "error": "need --model-dir and/or "
                                   "--decode-tiny"}), flush=True)
        return 2
    decode = None
    if args.decode_tiny is not None:
        from ..models import gpt
        from .decode import DecodeConfig, DecodeEngine

        mcfg = gpt.GPTConfig.tiny()
        mcfg.dtype = "float32"
        params, _ = gpt.init(jax.random.key(int(args.decode_tiny)), mcfg)
        decode = DecodeEngine(params, mcfg, DecodeConfig(
            block_size=8, num_blocks=64, decode_slots=(4,),
            prefill_buckets=(8, 16), precision="f32", max_len=64))
    buckets = tuple(int(b) for b in args.buckets.split(",")) \
        if args.buckets else None
    qos = None
    if args.qos:
        with open(args.qos) as f:
            qos = json.load(f)
    cfg = ServingConfig(
        args.model_dir or None, buckets=buckets,
        max_batch=args.max_batch,
        max_queue=args.max_queue, max_wait_ms=args.max_wait_ms,
        timeout_s=args.timeout_s, precision=args.precision,
        warmstart=args.warmstart or None, use_tpu=not args.cpu,
        host=args.host, qos=qos, model_id=args.model_id)
    server = Server(cfg, decode=decode)
    if args.registry:
        from .registry import ModelRegistry

        server.attach_registry(ModelRegistry(args.registry),
                               poll_s=args.registry_poll_s)
    port = server.start(args.port)
    endpoint = f"{args.host}:{port}"
    # env-gated time-series recording (PADDLE_TPU_TS_DIR): Server.start
    # already tried; call again explicitly so a replica records even
    # when the supervisor flips the env on between respawns
    from ..observability import timeseries as _timeseries

    _timeseries.maybe_start_recorder()

    rdzv = None
    rdzv_dir = args.rdzv_dir or os.environ.get("PADDLE_TPU_RDZV_DIR", "")
    if rdzv_dir:
        from ..distributed.rendezvous import FileRendezvous

        rdzv = FileRendezvous(rdzv_dir, worker_id=endpoint,
                              min_workers=1,
                              heartbeat_s=args.heartbeat_s,
                              dead_after_s=max(2.5,
                                               5 * args.heartbeat_s))
        rdzv.register()
        rdzv.start_heartbeat()

    stop_ev = threading.Event()

    def _on_term(signum, frame):
        stop_ev.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    print(json.dumps({
        "ready": True, "endpoint": endpoint, "pid": os.getpid(),
        "slot": args.slot,
        "warmstart_adopted":
            server._engine.warmstart_adopted
            if server._engine is not None else 0}), flush=True)

    stop_ev.wait()
    # graceful scale-in: stop being routable FIRST, then finish the
    # in-flight work, then tear down (SERVING.md §Fleet drain contract)
    if rdzv is not None:
        rdzv.leave()
    server.drain(timeout=args.drain_timeout_s)
    server.stop()
    # publish any buffered sampled spans before exit so the trace-dir
    # reassembly (obsdump trace) sees this replica's half of the tree,
    # and take the recorder's final time-series sample for the same
    # reason (a replica shorter than the interval must still record)
    from ..observability import tracing as _tracing

    _tracing.flush_trace_sink()
    _timeseries.stop_recorder()
    return 0


if __name__ == "__main__":
    sys.exit(main())
