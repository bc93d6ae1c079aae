#!/usr/bin/env python
"""warmstart — pre-bake a serving model's bucket executables offline.

A serving boot normally XLA-compiles every traffic bucket during
warmup; with a warmstart artifact the engine deserializes them instead,
so time-to-first-healthy is I/O-bound (SERVING.md §Warmstart). This
tool is the offline half: load the model, warm the full bucket set
once, and serialize the executables into one artifact the engine (or
`ServingConfig(warmstart=...)`) adopts at boot.

Usage:
  warmstart.py bake --model-dir DIR --out ART [--buckets 1,2,4,8]
                    [--max-batch N] [--cpu]
  warmstart.py bake-decode --out ART [--preset tiny] [--seed 0]
                    [--slots 4,8] [--prefill-buckets 8,16,32]
                    [--prefill-chunk C] [--spec-k K]
                    [--block-size 16] [--num-blocks N]
                    [--precision bf16] [--cpu]
  warmstart.py inspect ART

`bake-decode` (ISSUE 12) pre-bakes the decode engine's whole PHASE
GRID — every prefill-length bucket plus every decode slot-count
executable — so a decode serving boot replays the grid from I/O with
zero fresh compiles (`DecodeConfig(warmstart=...)`). The model is
rebuilt deterministically from --preset/--seed (jax PRNG is
reproducible across processes for a fixed jax version), and the
artifact is bound to the params digest + grid geometry, so a drifted
model or config is rejected at adoption, never silently served.

`--prefill-chunk` re-keys the grid for the chunked-prefill path
(SERVING.md §KV reuse): the per-prompt-length prefill buckets collapse
into one fixed-size chunk program, so the artifact carries
chunk+decode phases instead of bucket+decode phases. `--spec-k` adds
the speculative-decoding phases (draft prefill/decode + verify); the
draft is the same preset model (self-draft), deterministic from the
same --seed, so the digest binding still holds.

`bake` prints one JSON line: buckets warmed, entries serialized,
warmup seconds, artifact size. `inspect` reads only the artifact
(stdlib, no jax import) and prints its metadata + per-signature blob
sizes — what an operator checks before shipping the artifact to the
serving fleet. NOTE: artifacts are pickles; `inspect` unpickles, so
(like the engine) only run it on artifacts from the trusted channel
that carries the model files themselves.

The artifact is environment-bound (jax version, backend, device kind)
and model-bound (digest of __model__): the engine rejects a mismatched
artifact and falls back to compiling, so baking on the wrong machine
costs nothing but the cold boot it failed to avoid.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cmd_bake(args) -> int:
    sys.path.insert(0, _REPO)
    import jax

    if args.cpu:
        # use_tpu=False alone still compiles on the DEFAULT backend
        # (the Predictor's jax.jit), and artifacts are backend-stamped:
        # without this pin a TPU host would bake tpu-stamped blobs that
        # every CPU serving boot rejects. Must happen before any jax
        # use.
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.engine import Engine, ServingConfig

    buckets = None
    if args.buckets:
        try:
            buckets = sorted({int(b) for b in args.buckets.split(",")})
        except ValueError:
            print(f"bake: bad --buckets {args.buckets!r} (want e.g. "
                  f"1,2,4,8)", file=sys.stderr)
            return 2
    cfg = ServingConfig(args.model_dir, buckets=buckets,
                        max_batch=args.max_batch,
                        use_tpu=not args.cpu, aot=True)
    t0 = time.perf_counter()
    engine = Engine(cfg)
    ready = engine.warmup()
    warm_s = time.perf_counter() - t0
    n = engine.export_warmstart(args.out)
    print(json.dumps({
        "artifact": args.out,
        "model_dir": args.model_dir,
        "buckets": [int(b) for b in engine.policy.buckets],
        "buckets_ready": ready,
        "entries": n,
        "warmup_seconds": round(warm_s, 3),
        "artifact_bytes": os.path.getsize(args.out),
    }), flush=True)
    return 0 if n else 1


def cmd_bake_decode(args) -> int:
    sys.path.insert(0, _REPO)
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.models import gpt
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    if args.preset != "tiny":
        print(f"bake-decode: unknown --preset {args.preset!r} (only "
              "'tiny' is shipped; build bigger grids through the "
              "DecodeEngine API)", file=sys.stderr)
        return 2
    try:
        slots = sorted({int(s) for s in args.slots.split(",")})
        buckets = sorted({int(b) for b in
                          args.prefill_buckets.split(",")})
    except ValueError:
        print(f"bake-decode: bad --slots/--prefill-buckets (want e.g. "
              f"4,8)", file=sys.stderr)
        return 2
    cfg = gpt.GPTConfig.tiny()
    params, _ = gpt.init(jax.random.key(args.seed), cfg)
    max_len = args.max_len or cfg.max_len
    blocks_per_seq = -(-max_len // args.block_size)
    num_blocks = args.num_blocks or \
        (1 + max(slots) * blocks_per_seq)
    grid_kw = {}
    if args.prefill_chunk:
        # chunked path: the bucket dimension collapses into one chunk
        # program, so --prefill-buckets is ignored for the grid key
        grid_kw["prefill_chunk"] = args.prefill_chunk
    else:
        grid_kw["prefill_buckets"] = buckets
    dc = DecodeConfig(block_size=args.block_size, num_blocks=num_blocks,
                      decode_slots=slots, max_len=max_len,
                      precision=args.precision, spec_k=args.spec_k,
                      **grid_kw)
    # self-draft: same params serve as the draft model, so the baked
    # draft/verify phases stay deterministic from --preset/--seed
    draft = (params, cfg) if args.spec_k else None
    t0 = time.perf_counter()
    engine = DecodeEngine(params, cfg, dc, draft=draft)
    ready = engine.warmup()
    warm_s = time.perf_counter() - t0
    n = engine.export_warmstart(args.out)
    grid_out = {"decode_slots": slots, "spec_k": args.spec_k}
    if args.prefill_chunk:
        grid_out["prefill_chunk"] = args.prefill_chunk
    else:
        grid_out["prefill_buckets"] = buckets
    print(json.dumps({
        "artifact": args.out,
        "preset": args.preset, "seed": args.seed,
        "phase_grid": grid_out,
        "phases_ready": ready,
        "entries": n,
        "precision": args.precision,
        "warmup_seconds": round(warm_s, 3),
        "artifact_bytes": os.path.getsize(args.out),
    }), flush=True)
    return 0 if n else 1


def cmd_inspect(args) -> int:
    try:
        with open(args.artifact, "rb") as f:
            art = pickle.loads(f.read())
    # pickle.loads on a truncated/foreign stream raises well beyond
    # UnpicklingError (EOFError, ImportError, AttributeError, ...);
    # the operator check must print its diagnostic + rc=2, not a
    # traceback, for any of them
    except Exception as e:
        print(f"inspect: cannot read {args.artifact}: {e}",
              file=sys.stderr)
        return 2
    if not isinstance(art, dict) or "entries" not in art:
        print(f"inspect: {args.artifact} is not a warmstart artifact",
              file=sys.stderr)
        return 2
    # a dict with "entries" can still be structurally malformed
    # (tampered/truncated-then-repickled, or a future format): the
    # same diagnostic-not-traceback contract applies to shape errors
    # as to unpickling errors
    try:
        entries = art["entries"]
        if art.get("format") == "paddle_tpu-decode-warmstart-v1":
            # decode artifacts key entries by phase ("prefill", T) /
            # ("decode", S), not by feed signature
            signatures = [
                {"phase": f"{kind}@{n}",
                 "blob_bytes": len(e["blob"]),
                 "fingerprint": (e.get("fingerprint") or "")[:16]}
                for (kind, n), e in sorted(entries.items())]
        else:
            signatures = [
                {"feeds": [f"{n}:{list(s)}:{d}" for n, s, d in sig],
                 "blob_bytes": len(e["blob"]),
                 "fingerprint": (e.get("fingerprint") or "")[:16]}
                for sig, e in sorted(entries.items())]
        report = {
            "format": art.get("format"),
            "jax_version": art.get("jax_version"),
            "backend": art.get("backend"),
            "device_kind": art.get("device_kind"),
            "model_digest": art.get("model_digest"),
            "buckets": art.get("buckets"),
            "phase_grid": art.get("grid"),
            "created_at": art.get("created_at"),
            "entries": len(entries),
            "signatures": signatures,
        }
    except Exception as e:
        print(f"inspect: {args.artifact} has malformed entries: {e!r}",
              file=sys.stderr)
        return 2
    print(json.dumps(report, indent=2))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="warmstart", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    bp = sub.add_parser("bake", help="warm every bucket and serialize "
                        "the executables into one artifact")
    bp.add_argument("--model-dir", required=True,
                    help="saved inference model directory")
    bp.add_argument("--out", required=True, help="artifact path")
    bp.add_argument("--buckets", default=None,
                    help="comma-separated batch buckets (default: pow2 "
                    "up to --max-batch)")
    bp.add_argument("--max-batch", type=int, default=64)
    bp.add_argument("--cpu", action="store_true",
                    help="bake for the CPU backend (artifacts are "
                    "backend-bound)")
    bp.set_defaults(fn=cmd_bake)

    dp = sub.add_parser("bake-decode", help="pre-bake a decode "
                        "engine's full phase grid (prefill buckets + "
                        "decode slot configs) into one artifact")
    dp.add_argument("--out", required=True, help="artifact path")
    dp.add_argument("--preset", default="tiny",
                    help="model preset (deterministic from --seed)")
    dp.add_argument("--seed", type=int, default=0)
    dp.add_argument("--slots", default="4,8",
                    help="comma-separated decode slot counts")
    dp.add_argument("--prefill-buckets", default="8,16,32",
                    help="comma-separated prompt-length buckets")
    dp.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill slice size; collapses the "
                    "prefill buckets into one chunk phase (0 = off)")
    dp.add_argument("--spec-k", type=int, default=0,
                    help="speculative-decoding draft length; bakes the "
                    "draft + verify phases with a self-draft (0 = off)")
    dp.add_argument("--block-size", type=int, default=16)
    dp.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool blocks (default: worst-case for the "
                    "slot count)")
    dp.add_argument("--max-len", type=int, default=None)
    dp.add_argument("--precision", default="bf16",
                    choices=("f32", "bf16"))
    dp.add_argument("--cpu", action="store_true",
                    help="bake for the CPU backend (artifacts are "
                    "backend-bound)")
    dp.set_defaults(fn=cmd_bake_decode)

    ip = sub.add_parser("inspect", help="print an artifact's metadata "
                        "(no jax import)")
    ip.add_argument("artifact")
    ip.set_defaults(fn=cmd_inspect)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
