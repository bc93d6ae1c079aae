"""Continuous-batching autoregressive decode engine.

The PR 3 serving stack buckets fixed-shape one-shot predicts; token
generation through it would re-run the full forward per token. This
module is the token-serving half the ROADMAP calls the flagship
workload: a decode engine that composes the substrate the repo already
owns —

- **paged KV cache** (kv_cache.py): fixed-size blocks in ONE
  preallocated device pool, per-sequence block tables, blocks
  allocated on admit / freed on finish, so HBM scales with live
  tokens, not max_seq_len × batch; and, for a model with recurrent
  layers, STATE ROW pools beside it: a fixed-size row a sequence that
  its prefill overwrites and every decode step advances in place,
  addressed by a row id handed to the step as block tables are;
- **continuous (in-flight) batching** (ORCA OSDI'22): the scheduler
  admits new requests into the RUNNING decode batch every step and
  retires finished ones without draining it;
- **prefill/decode phase split**: prompts run through per-length
  prefill buckets (the existing BucketPolicy idea applied to sequence
  length), decode always runs at one of a few fixed slot counts — so
  the whole phase grid is a small closed signature set that is
  AOT-warmed once, pre-baked into a PR 6 warmstart artifact
  (`export_warmstart`/`load_warmstart`, `tools/warmstart.py
  bake-decode`), and replayed at boot with zero fresh compiles;
- **lazy token fetches** (PR 5 FetchHandle): each decode step's
  sampled tokens resolve one step LATE — step N dispatches with step
  N-1's tokens still device-resident, and so does an admission's first
  token: a prefill is queued behind the step in flight, the next step's
  ids are put together on the device, and the host never blocks the
  device between steps, whether the batch composition is stable or an
  admission or a retirement just changed it;
- **PR 7 precision policies**: bf16 decode by default (pools + compute
  dtype), f32 opt-in for exactness; the policy is part of every
  executable's signature and persistent-cache fingerprint;
- **PR 8 boot validation**: config + trace findings in the analysis
  Finding shape, PADDLE_TPU_VALIDATE=2 refuses to serve a broken grid.

Sampling is greedy (beam_size=1) through `ops/beam.beam_search`, whose
finished-freeze semantics keep an ended slot emitting eos without
host-side branching. When the pool runs dry mid-decode, the youngest
active sequence is preempted vLLM-style: blocks freed, request
re-queued with prompt+generated-so-far, re-prefilled later (already
streamed tokens are not re-emitted).
"""

from __future__ import annotations

import collections
import hashlib
import pickle
import queue
import threading
import time
import weakref
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import compile_cache as _cc
from ..core import precision as _precision
from ..core.async_exec import FetchHandle
from ..core.executor import _JitDispatch
from ..observability import events as _events
from ..observability import memwatch as _memwatch
from ..observability import metrics as _m
from ..observability import perfwatch as _perfwatch
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from ..ops.pallas import attention as _attention
from ..ops.pallas import grouped_matmul as _grouped_matmul
from ..ops.pallas import paged_attention as _paged_attention
from ..ops.pallas import ssm_update as _ssm_update
from .batcher import QueueFullError, ServerClosed
from .kv_cache import (NULL_ROW, PREFILL_WRITE_UNITS, BlockAllocator,
                       KVCacheConfig, NoBlocksError, StateRowAllocator,
                       build_block_table, init_pools, ring_blocks,
                       run_chunks, window_table)
from . import kv_reuse as _kvr
from .kv_reuse import ReuseBlockAllocator

if TYPE_CHECKING:  # runtime import is deferred (package bootstrap)
    from .qos import WeightedFairScheduler

__all__ = ["DecodeConfig", "DecodeEngine", "DecodeHandle",
           "DECODE_WARMSTART_FORMAT"]

DECODE_WARMSTART_FORMAT = "paddle_tpu-decode-warmstart-v1"

QUEUE_DEPTH = _m.gauge(
    "paddle_tpu_decode_queue_depth",
    "Requests waiting for a decode slot")
SLOTS = _m.gauge(
    "paddle_tpu_decode_slots",
    "Decode slots (state=active|configured)", labelnames=("state",))
KV_BLOCKS = _m.gauge(
    "paddle_tpu_decode_kv_blocks",
    "KV-cache pool blocks (state=used|free)", labelnames=("state",))
# a fifth apart from 1 ms to 1 s, so that a bucket quantile of a 10 ms or a
# 100 ms step is good to 20% (the default buckets step from 0.1 to 0.25 s);
# the tail keeps a stall's size readable
_LATENCY_BUCKETS = _m.exponential_buckets(1e-3, 1.2, 39) + (
    2.5, 5.0, 10.0, 30.0, 60.0)
TTFT_SECONDS = _m.histogram(
    "paddle_tpu_decode_ttft_seconds",
    "Submit-to-first-token latency (prefill completion)",
    buckets=_LATENCY_BUCKETS)
STEP_SECONDS = _m.histogram(
    "paddle_tpu_decode_step_seconds",
    "Wall seconds from a decode step's dispatch to its tokens on the "
    "host: dispatch to resolve, about two device steps under the lazy "
    "loop's overlap, not a step's time (status()['step_ms'] is start to "
    "start)",
    buckets=_LATENCY_BUCKETS)
TOKENS = _m.counter(
    "paddle_tpu_decode_tokens_total",
    "Tokens sampled (phase=prefill|decode)", labelnames=("phase",))
STEPS = _m.counter(
    "paddle_tpu_decode_steps_total",
    "Phase executions (phase=prefill|decode|draft|verify)",
    labelnames=("phase",))
REQUESTS = _m.counter(
    "paddle_tpu_decode_requests_total",
    "Finished requests by outcome (eos|length|rejected|cancelled|error)",
    labelnames=("outcome",))
PREEMPTIONS = _m.counter(
    "paddle_tpu_decode_preemptions_total",
    "Sequences preempted back to the queue on KV-pool pressure")
OCCUPANCY = _m.histogram(
    "paddle_tpu_decode_slot_occupancy",
    "Active slots / compiled slot count per decode step",
    buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))


def _pow2_lengths(lo: int, hi: int) -> Tuple[int, ...]:
    out, b = [], int(lo)
    while b < hi:
        out.append(b)
        b *= 2
    out.append(int(hi))
    return tuple(sorted(set(out)))


class DecodeConfig:
    """Knobs for the decode engine (SERVING.md §Continuous batching).

    decode_slots: the fixed slot counts decode executables exist for;
    each step runs at the smallest config >= live sequences.
    prefill_buckets: prompt-length buckets (pow2 from 8 up to max_len
    by default); a prompt pads to the smallest bucket that fits.
    num_blocks/block_size: the KV pool (block 0 is the null block).

    KV-reuse knobs (SERVING.md §KV reuse): prefill_chunk > 0 replaces
    the prefill-bucket grid with ONE fixed-size chunk executable —
    prompts prefill in slices interleaved with decode steps;
    prefix_cache=True (requires prefill_chunk) makes the allocator
    ref-counted with a content-hash index so shared prompt prefixes
    resolve to live pool blocks; spec_k > 0 (requires a draft model
    passed to DecodeEngine) proposes k tokens per step through the
    draft and verifies them in one batched target step with exact
    greedy accept/reject. Any of these switches the engine onto the
    synchronous reuse scheduler (no lazy-fetch overlap)."""

    def __init__(self, *, block_size: int = 16, num_blocks: int = 64,
                 decode_slots: Sequence[int] = (4, 8),
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_len: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 max_queue: int = 64,
                 precision: str = "bf16",
                 warmstart: Optional[str] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: int = 0,
                 spec_k: int = 0,
                 qos=None,
                 model_tag: Optional[str] = None):
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.decode_slots = tuple(sorted({int(s) for s in decode_slots}))
        self.prefill_buckets = tuple(sorted({int(b) for b in
                                             prefill_buckets})) \
            if prefill_buckets is not None else None
        self.max_len = max_len
        self.eos_id = eos_id
        self.max_queue = int(max_queue)
        self.precision = str(precision)
        self.warmstart = warmstart
        self.prefix_cache = bool(prefix_cache)
        self.prefill_chunk = int(prefill_chunk)
        self.spec_k = int(spec_k)
        # per-tenant QoS policy (a qos.QoSPolicy or its from_spec dict;
        # None = single-tenant FIFO) — SERVING.md §Multi-tenancy
        self.qos = qos
        # memwatch owner suffix for multi-model processes: with
        # model_tag="m", HBM providers register as "kv_pool[m]" etc. so
        # per-model KV/param footprints stay attributable while sharing
        # one process budget
        self.model_tag = model_tag
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got "
                             f"{self.prefill_chunk}")
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.prefix_cache and not self.prefill_chunk:
            raise ValueError(
                "prefix_cache=True requires prefill_chunk > 0: reused "
                "prefixes start the computed suffix mid-prompt, which "
                "only the chunked (gather-attention) prefill program "
                "supports")


class DecodeHandle:
    """Client side of one generation: a thread-safe token stream.

    `tokens()` yields token ids as the scheduler emits them and ends
    when the request finishes; `result(timeout_s)` collects them all.
    `info` fills in as generation progresses (ttft_s, finish_reason,
    n_tokens)."""

    def __init__(self, req: "_Request"):
        self._req = req

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def t_first(self) -> Optional[float]:
        """CLOCK_MONOTONIC time of the first token's emission."""
        return self._req.t_first

    @property
    def info(self) -> Dict:
        r = self._req
        return {
            "prompt_len": int(r.prompt_len0),
            "n_tokens": len(r.generated),
            "ttft_s": (r.t_first - r.t_submit) if r.t_first else None,
            "finish_reason": r.finish_reason,
        }

    def tokens(self, timeout_s: Optional[float] = None):
        deadline = (time.monotonic() + timeout_s) if timeout_s else None
        while True:
            left = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            try:
                item = self._req.events.get(timeout=left)
            except queue.Empty:
                raise TimeoutError(
                    f"generation produced no token within {timeout_s}s")
            if item is None:
                if self._req.error is not None:
                    raise self._req.error
                return
            yield item

    def result(self, timeout_s: Optional[float] = None) -> List[int]:
        return list(self.tokens(timeout_s=timeout_s))


class _Request:
    __slots__ = ("rid", "prompt", "prompt_len0", "max_new", "generated",
                 "events", "t_submit", "t_first", "finish_reason",
                 "error", "cancelled", "last_token", "pos", "blocks",
                 "admitted_at", "tctx", "enqueued_at",
                 "prefill_pos", "draft_pos", "n_reused", "hashes",
                 "tenant", "traced", "parent", "arrival", "preempted",
                 "state_row", "wblocks")

    def __init__(self, rid: int, prompt: np.ndarray, max_new: int,
                 tenant: str = "default"):
        self.tenant = tenant
        self.rid = rid
        # captured on the submitter's thread; the scheduler thread
        # records queue-wait/prefill/TTFT spans against it later
        self.tctx = _tracing.current_trace()
        # request-level span sites record when recording is on OR the
        # request's context is sampled (the distributed trace)
        self.traced = self.tctx is not None and self.tctx.sampled
        # the span open on the submitter's thread (the handler's
        # http.generate) is the parent of every span recorded for this
        # request; its start is the request's arrival at the front
        self.parent = self.arrival = None
        if _tracing.recording:
            cause = _tracing.current_span()
            if cause is not None:
                self.parent, self.arrival = cause.sid, cause.t0
        self.preempted = 0
        self.prompt = prompt                   # grows on preempt-replay
        self.prompt_len0 = len(prompt)         # original, for reporting
        self.max_new = int(max_new)
        self.generated: List[int] = []
        # the token stream to the request's reader. A SimpleQueue: `put`
        # and a blocked `get` are single C calls, where `queue.Queue` takes
        # a Python-level mutex and condition on either side, a handful of
        # interpreter-lock handoffs a token a stream that the decode loop
        # pays for once 64 streams are read at once (PR 34)
        self.events: "queue.SimpleQueue" = queue.SimpleQueue()
        self.t_submit = time.monotonic()
        self.enqueued_at = self.t_submit   # re-stamped on preempt requeue
        self.t_first: Optional[float] = None
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.cancelled = False
        # slot state (meaningful while active)
        self.last_token = 0
        self.pos = 0                           # next KV write position
        self.blocks: List[int] = []
        self.wblocks: List[int] = []           # its ring of the window kind
        self.state_row = NULL_ROW              # its row of the state pools
        self.admitted_at = 0.0
        # KV-reuse state (chunked prefill / prefix cache / speculation)
        self.prefill_pos = 0     # next prompt position to chunk-prefill
        self.draft_pos = 0       # next DRAFT KV write position
        self.n_reused = 0        # prefix blocks resolved from the cache
        self.hashes = None       # chain hashes of the prompt's blocks


class _Pending:
    """One program in flight on the device's queue and the lazy fetch of
    its tokens: a decode step with the exact batch it was dispatched with
    (`slots`, and `snapshot` their rids), or an admission's prefill
    (`slots` the one request, `snapshot` None) whose first token is still
    on the device. The lazy loop keeps them in `_inflight` in device
    order and resolves them oldest first."""

    __slots__ = ("handle", "tok_dev", "snapshot", "slots", "t_dispatch",
                 "stats")

    def __init__(self, tok_dev, snapshot, slots, t_dispatch=None):
        self.handle = FetchHandle([tok_dev], site="decode")
        self.tok_dev = tok_dev
        self.snapshot = snapshot               # tuple of rids (padded -1)
        self.slots = slots                     # list of Optional[_Request]
        # a prefill's clock starts before its call, as it always has
        self.t_dispatch = time.perf_counter() if t_dispatch is None \
            else t_dispatch
        # recording on, and the model counts: (the step's record, its
        # counters still on the device)
        self.stats = None


def _assemble_ids(prev, first, idx):
    """ids [C] of the next decode step, on the device: row `idx[i]` of
    the tokens `prev` [Cp] with one prefill's first token [1] behind
    them."""
    return jnp.take(jnp.concatenate([prev, first]), idx, mode="clip")


def _nbytes(arrays) -> int:
    return sum(int(a.nbytes) for a in arrays)


def _phase_name(key) -> str:
    """`("decode", 16)` -> `"decode@16"`, `("assemble", (16, 32))` ->
    `"assemble@16x32"`: a phase key as boot spans and messages name it."""
    kind, n = key
    return f"{kind}@" + ("x".join(map(str, n)) if isinstance(n, tuple)
                         else str(n))


def _boot_status() -> Dict:
    """`status()["boot"]`: the tracing's summary of the process's boot,
    and of the boot spans this file owns `ready_s` (the process's start to
    the end of the last warm-up: what a replica's autoscaler waits for)
    and `phases` (every warm-up phase, oldest first: its seconds and how
    its executable got there). A `status()` may be polled: the view is
    made anew only when a kept row has been added."""
    global _boot_view
    version = _tracing.kept_rows()
    if _boot_view is None or _boot_view[0] != version:
        boot = _tracing.boot_summary()
        spans = _tracing.get_records("boot.spans")
        warm = [s["t1"] for s in spans if s["name"] == "boot.engine_warmup"]
        boot["ready_s"] = max(warm) - boot["process_start"] if warm \
            else None
        boot["phases"] = [
            {"phase": s.get("phase"), "installed": s.get("installed"),
             "seconds": s["t1"] - s["t0"]}
            for s in spans if s["name"] == "boot.warm_phase"]
        _boot_view = (version, boot)
    return dict(_boot_view[1])


_boot_view: Optional[Tuple[int, Dict]] = None


class DecodeEngine:
    """Continuous-batching token generation over a paged KV cache.

    Built from in-memory model state: `params` and a `model_cfg` whose
    `serve_model()` is the `models/decoder.ServeModel` of its block
    (`models.gpt` dense configs, `models.olmoe`). `submit()` is
    thread-safe and reject-not-block (QueueFullError when `max_queue` prompts wait);
    one scheduler thread owns the device pools, the allocator, and
    every phase dispatch."""

    def __init__(self, params, model_cfg, config: Optional[DecodeConfig]
                 = None, draft=None):
        # kept whether or not a recording is on: `status()["boot"]` and a
        # cold start's account read it long after
        with _tracing.boot_span("boot.engine_build") as facts:
            self._build(params, model_cfg, config, draft)
            n_rows = len(self._row_specs)
            facts.update(weight_bytes=_nbytes(self.params.values()),
                         pool_bytes=_nbytes(self._pools)
                         + _nbytes(self._state[n_rows:]),
                         state_bytes=_nbytes(self._state[:n_rows]))

    def _build(self, params, model_cfg, config, draft):
        from ..models import decoder as _decoder

        self.config = config or DecodeConfig()
        self.model_cfg = model_cfg
        # the model as the engine drives it (models/decoder.ServeModel):
        # the cache's shape and the block's pieces of the four programs
        self._model = model = model_cfg.serve_model()
        _cc.key_on_metadata()   # the phase grid's scopes are read in profiles
        self.prefill_chunk = int(getattr(self.config, "prefill_chunk",
                                         0))
        self.spec_k = int(getattr(self.config, "spec_k", 0))
        if self.spec_k and draft is None:
            raise ValueError(
                "spec_k > 0 requires a draft model: pass "
                "DecodeEngine(..., draft=(draft_params, draft_cfg))")
        if draft is not None and not self.spec_k:
            raise ValueError(
                "a draft model was passed but spec_k == 0; set "
                "DecodeConfig(spec_k=k) to enable speculation")
        # any reuse feature runs the synchronous scheduler (_loop_sync)
        self._sync = bool(self.prefill_chunk or self.spec_k)
        rows = max(self.config.decode_slots) + 1    # + the null row
        if model.state_pools(rows, np.dtype("float32")) or model.rated:
            # a sequence's recurrent state is ONE row, the state after its
            # last token: nothing in it can be shared, resumed or unwound
            refused = [why for on, why in (
                (self.config.prefix_cache,
                 "prefix_cache: a shared prefix's blocks hold its K/V, but "
                 "the recurrent state after the prefix is not kept (it "
                 "needs a snapshot a cached prefix), so a reused prefix "
                 "would start from no state"),
                (self.prefill_chunk,
                 "prefill_chunk: a prompt's slices would have to carry the "
                 "recurrent state from one to the next (the prefill "
                 "program starts from a zero state and writes the row "
                 "once)"),
                (self.spec_k,
                 "spec_k: a rejected draft token has already advanced the "
                 "recurrent state, and there is no snapshot to step back "
                 "to"),
            ) if on]
            if refused:
                raise ValueError(
                    "a model with recurrent state, or with entries stored "
                    "at a rate beside a token's two, cannot be served with "
                    + "; ".join(refused))
        if model.window:
            # a window layer's ring holds the newest keys of ONE sequence
            refused = [why for on, why in (
                (self.config.prefix_cache,
                 "prefix_cache: a shared prefix's blocks would have to hold "
                 "its window layers' K/V, and a ring keeps the newest "
                 "window alone, overwritten as the sequence grows: nothing "
                 "of a prefix is left to share"),
                (self.prefill_chunk,
                 "prefill_chunk: a ring is sized for the window and ONE "
                 "slice of the prefill program's own walk, and the chunk "
                 "program gathers a sequence's whole table"),
                (self.spec_k,
                 "spec_k: a rejected draft token's K/V has already "
                 "overwritten the ring's oldest key, which the step after "
                 "still reads"),
            ) if on]
            if refused:
                raise ValueError(
                    "a model with a window kind of cache cannot be served "
                    "with " + "; ".join(refused))
            if not model.prompt_slice:
                raise ValueError(
                    "a model with a window kind of cache walks its prompts "
                    "in slices (`ServeModel.prompt_slice`)")
        if self.config.precision not in ("f32", "bf16"):
            _precision.get_policy(self.config.precision)  # typo => full msg
            raise ValueError(
                f"unsupported decode precision "
                f"{self.config.precision!r}; choose from ['f32', 'bf16']")
        policy = _precision.get_policy(
            "bf16" if self.config.precision == "bf16" else "f32")
        self._compute_dtype = policy.compute_dtype or np.dtype("float32")
        self.params = {
            k: _precision.cast_floating(v, self._compute_dtype)
            for k, v in params.items()}
        max_len = int(self.config.max_len or model.max_len)
        self.kv_cfg = KVCacheConfig(
            layers=model.kv_layers, widths=model.stored,
            max_len=max_len, block_size=self.config.block_size,
            num_blocks=self.config.num_blocks,
            dtype=str(np.dtype(self._compute_dtype)),
            rated=tuple(model.rated))
        # the model's state pools ((shape, dtype), ...): () for a model
        # whose sequences keep nothing but their blocks, and then no
        # program takes or returns anything more than it did
        self._row_specs = tuple(
            (tuple(shape), np.dtype(dt)) for shape, dt in
            model.state_pools(rows, np.dtype(self._compute_dtype)))
        # the programs carry the pools of the entries stored at a rate
        # (`ServeModel.rated`: they follow the block tables, not the rows)
        # behind the row pools, in the one donated `state`
        self._state_specs = self._row_specs + tuple(
            (shape, np.dtype(self._compute_dtype))
            for shape in self.kv_cfg.rated_pool_shapes)
        # the WINDOW kind (`ServeModel.window`): pools, an allocator and a
        # table a sequence of its own, sized HERE from the model and the
        # slots (`num_blocks` is the global kind's): a ring a slot and the
        # null block. Its two pools ride last in `state`.
        self._wkv_cfg = None
        self._ring = 0
        if model.window:
            self._ring = min(
                ring_blocks(model.window, model.prompt_slice,
                            self.config.block_size),
                self.kv_cfg.max_blocks_per_seq)
            self._wkv_cfg = KVCacheConfig(
                layers=model.window_layers, widths=model.stored,
                max_len=max_len, block_size=self.config.block_size,
                num_blocks=max(self.config.decode_slots) * self._ring + 1,
                dtype=str(np.dtype(self._compute_dtype)))
            self._state_specs += tuple(
                (shape, np.dtype(self._compute_dtype))
                for shape in self._wkv_cfg.pool_shapes)
        # resolved grid lives on the ENGINE, never written back into
        # the caller's config (a DecodeConfig reused across engines
        # must not carry the first engine's derived bucket set)
        self.prefill_buckets = self.config.prefill_buckets \
            if self.config.prefill_buckets is not None \
            else _pow2_lengths(min(8, max_len), max_len)
        self.decode_slots = self.config.decode_slots
        self.eos_id = -1 if self.config.eos_id is None \
            else int(self.config.eos_id)

        # -- draft model (speculative decoding) -----------------------
        # its pools share num_blocks/block_size/max_len with the target
        # so BLOCK TABLES ARE SHARED: one allocation covers both models
        # and prefix-cache hits resolve both models' prompt KV at once
        self._draft = draft
        self._draft_params = None
        self._draft_cfg = None
        self._draft_model = None
        self._draft_kv_cfg = None
        if draft is not None:
            draft_params, draft_cfg = draft
            self._draft_cfg = draft_cfg
            self._draft_model = dmodel = draft_cfg.serve_model()
            self._draft_params = {
                k: _precision.cast_floating(v, self._compute_dtype)
                for k, v in draft_params.items()}
            self._draft_kv_cfg = KVCacheConfig(
                layers=dmodel.kv_layers, widths=dmodel.stored,
                max_len=max_len, block_size=self.config.block_size,
                num_blocks=self.config.num_blocks,
                dtype=str(np.dtype(self._compute_dtype)))

        # -- phase grid: one dispatcher per (phase, size) -------------
        bs = self.kv_cfg.block_size
        pol = None if self.config.precision == "f32" \
            else self.config.precision

        # `state`: the state pools and the row id(s), for a model that has
        # them: (tok, k_pool, v_pool[, counters], state) come back
        def _prefill_fn(p, ids, length, kp, vp, bt, *state):
            return _decoder.prefill(model, p, ids, length, kp, vp, bt,
                                    *state, block_size=bs,
                                    eos_id=self.eos_id)

        # (tokens, k_pool, v_pool, the model's per-layer counters or None)
        def _decode_fn(p, ids, positions, kp, vp, bts, *state):
            return _decoder.decode_step(model, p, ids, positions, kp, vp,
                                        bts, *state, block_size=bs,
                                        eos_id=self.eos_id)

        # the pools are donated and updated in place, the state pools too
        donate = (3, 4, 6) if self._state_specs else (3, 4)

        def _chunk_fn(p, ids, start, length, kp, vp, bt):
            return _decoder.prefill_chunk(
                model, p, ids, start, length, kp, vp, bt,
                block_size=bs, eos_id=self.eos_id)

        # chunked prefill COLLAPSES the prompt-length bucket dimension:
        # the grid carries one chunk executable instead of one program
        # per bucket (warmstart artifacts re-key accordingly)
        self._chunk: Dict[int, _JitDispatch] = {}
        self._prefill: Dict[int, _JitDispatch] = {}
        if self.prefill_chunk:
            self._chunk = {
                self.prefill_chunk: _JitDispatch(
                    jax.jit(_chunk_fn, donate_argnums=(4, 5)),
                    "prefill", meta={"chunk": self.prefill_chunk},
                    policy=pol)}
        else:
            self._prefill = {
                t: _JitDispatch(jax.jit(_prefill_fn,
                                        donate_argnums=donate),
                                "prefill", meta={"bucket": int(t)},
                                policy=pol)
                for t in self.prefill_buckets}
        self._decode: Dict[int, _JitDispatch] = {
            s: _JitDispatch(jax.jit(_decode_fn, donate_argnums=donate),
                            "decode", meta={"slots": int(s)}, policy=pol)
            for s in self.decode_slots}

        # the lazy loop's id assembly (`_next_ids`): after an admission
        # or a retirement the next step's ids are put together on the
        # device, one program a pair of slot configurations (the step in
        # flight, the step to come)
        self._assemble: Dict[Tuple[int, int], _JitDispatch] = {} \
            if self._sync else {
                (a, b): _JitDispatch(jax.jit(_assemble_ids), "decode",
                                     meta={"assemble": [int(a), int(b)]},
                                     policy=pol)
                for a in self.decode_slots for b in self.decode_slots}

        self._draft_prefill: Dict[int, _JitDispatch] = {}
        self._draft_chunk: Dict[int, _JitDispatch] = {}
        self._draft_decode: Dict[int, _JitDispatch] = {}
        self._verify: Dict[int, _JitDispatch] = {}
        if draft is not None:
            def _dprefill_fn(p, ids, length, kp, vp, bt):
                return _decoder.prefill(dmodel, p, ids, length, kp, vp,
                                        bt, block_size=bs,
                                        eos_id=self.eos_id)

            def _ddecode_fn(p, ids, positions, kp, vp, bts):
                return _decoder.decode_step(
                    dmodel, p, ids, positions, kp, vp, bts, block_size=bs,
                    eos_id=self.eos_id)[:3]

            def _dchunk_fn(p, ids, start, length, kp, vp, bt):
                return _decoder.prefill_chunk(
                    dmodel, p, ids, start, length, kp, vp, bt,
                    block_size=bs, eos_id=self.eos_id)

            def _verify_fn(p, ids, positions, kp, vp, bts):
                return _decoder.verify_step(
                    model, p, ids, positions, kp, vp, bts,
                    block_size=bs, eos_id=self.eos_id)

            if self.prefill_chunk:
                self._draft_chunk = {
                    self.prefill_chunk: _JitDispatch(
                        jax.jit(_dchunk_fn, donate_argnums=(4, 5)),
                        "prefill",
                        meta={"chunk": self.prefill_chunk,
                              "draft": True}, policy=pol)}
            else:
                self._draft_prefill = {
                    t: _JitDispatch(
                        jax.jit(_dprefill_fn, donate_argnums=(3, 4)),
                        "prefill", meta={"bucket": int(t),
                                         "draft": True}, policy=pol)
                    for t in self.prefill_buckets}
            self._draft_decode = {
                s: _JitDispatch(
                    jax.jit(_ddecode_fn, donate_argnums=(3, 4)),
                    "decode", meta={"slots": int(s), "draft": True},
                    policy=pol)
                for s in self.decode_slots}
            self._verify = {
                s: _JitDispatch(
                    jax.jit(_verify_fn, donate_argnums=(3, 4)),
                    "decode", meta={"verify": int(s), "k": self.spec_k},
                    policy=pol)
                for s in self.decode_slots}

        self.analysis = self._validate_boot()

        self._pools = init_pools(self.kv_cfg)
        self._state = tuple(jnp.zeros(shape, dt)
                            for shape, dt in self._state_specs)
        self._state_alloc = StateRowAllocator(rows, self._row_specs) \
            if self._row_specs else None
        self._walloc = BlockAllocator(self._wkv_cfg) \
            if self._wkv_cfg is not None else None
        self._draft_pools = init_pools(self._draft_kv_cfg) \
            if draft is not None else None
        # annotated with the reuse subtype so the lock-order analyzer
        # (tools/lockgraph.py) sees its leaf lock acquired under _cv
        self._alloc: "ReuseBlockAllocator" = \
            ReuseBlockAllocator(self.kv_cfg) \
            if self.config.prefix_cache else BlockAllocator(self.kv_cfg)
        # COW device copy: src block's contents into dst across both
        # pools (shape-cached jit; src/dst are traced scalars so every
        # copy reuses one executable per pool geometry). The pools are
        # donated: one block is written in place, not two pools copied.
        self._copy_block_fn = jax.jit(
            lambda kp, vp, src, dst: (kp.at[:, dst].set(kp[:, src]),
                                      vp.at[:, dst].set(vp[:, src])),
            donate_argnums=(0, 1))
        self._device_kind = getattr(jax.devices()[0], "device_kind",
                                    "unknown")
        # HBM owner attribution: providers hand memwatch the CURRENT
        # pool/param arrays on every sweep — donation replaces the pool
        # buffers each step, so a one-time registration of the arrays
        # themselves would go stale immediately. Weakref'd so a dropped
        # engine (tests build many) never pins its pools alive.
        ref = weakref.ref(self)

        def _kv_arrays():
            eng = ref()
            if eng is None:
                return ()
            out = list(eng._pools)
            if eng._draft_pools is not None:
                out.extend(eng._draft_pools)
            return out

        def _param_arrays():
            eng = ref()
            if eng is None:
                return ()
            out = list(eng.params.values())
            if eng._draft_params is not None:
                out.extend(eng._draft_params.values())
            return out

        # per-model owner attribution: engines sharing a process (the
        # multi-model Server) tag their providers with the model id so
        # memwatch's owner table splits the shared HBM budget by model
        tag = getattr(self.config, "model_tag", None)
        own = (lambda base: f"{base}[{tag}]") if tag else (lambda b: b)
        self._mem_handles = [
            _memwatch.register_provider(own("kv_pool"), _kv_arrays),
            _memwatch.register_provider(own("params"), _param_arrays)]
        if self._state_specs:
            # the row pools and, behind them, the pools of the entries
            # stored at a rate
            def _state_arrays():
                eng = ref()
                return () if eng is None else list(eng._state)

            self._mem_handles.append(_memwatch.register_provider(
                own("state_pool"), _state_arrays))
        if self.config.prefix_cache:
            # retained-prefix accounting: bytes of cached (unreferenced
            # but evictable) blocks across BOTH models' pools. These
            # bytes live INSIDE the kv_pool arrays — memwatch reports
            # them alongside, like executable_bytes, without double-
            # counting them into the live-array total.
            per_block = self._prefix_block_bytes()

            def _prefix_bytes():
                eng = ref()
                if eng is None:
                    return (0, 0)
                n = eng._alloc.cached_blocks()
                return (n * per_block, n)

            self._mem_handles.append(_memwatch.register_bytes_provider(
                own("prefix_cache"), _prefix_bytes))
        # deferred import: the analysis package must not load during
        # package bootstrap; constructors only run after it
        from ..analysis import lockcheck as _lockcheck

        self._cv = _lockcheck.Condition(
            name="serving.decode.DecodeEngine._cv")
        # per-tenant QoS (None = the historical single-tenant FIFO).
        # Deferred import: qos.py pulls QueueFullError from batcher.
        from . import qos as _qos_mod

        self._qosm = _qos_mod
        self._qos = _qos_mod.QoSPolicy.from_spec(
            getattr(self.config, "qos", None))
        # annotated so tools/lockgraph.py can type the attribute (the
        # conditional value defeats constructor inference)
        self._wfq: Optional["WeightedFairScheduler"] = \
            _qos_mod.WeightedFairScheduler(self._qos) \
            if self._qos is not None else None
        self._waiting: "collections.deque[_Request]" = collections.deque()
        self._active: List[_Request] = []
        # chunked-prefill stage: admitted (blocks reserved) but not yet
        # fully prefilled; the sync loop advances the FRONT request one
        # chunk per iteration, interleaved with decode steps
        self._prefilling: "collections.deque[_Request]" = \
            collections.deque()
        # the lazy loop: what is on the device's queue and not yet on the
        # host, in device order (decode step, prefill, decode step, ...)
        self._inflight: "collections.deque[_Pending]" = collections.deque()
        # how each decode step of the lazy loop got its ids, and the
        # forced resolves of everything in flight (status()["pipeline"]);
        # `starved`: dispatches of a recording that saw the device's queue
        # empty (`_queue_empty`)
        self._pipeline = {"fed": 0, "assembled": 0, "host": 0, "drains": 0,
                          "starved": 0}
        self._no_first = np.zeros((1,), np.int32)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._closed = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        # what the lazy loop's turn resolved and has not handed to the
        # readers yet (`_to_reader`); None outside its thread
        self._outbox: Optional[List] = None
        self._rid = 0
        self._last_slot_config: Optional[int] = None
        # start times of the last decode dispatches: status() reads the
        # exact median and p95 step time that STEP_SECONDS' buckets
        # cannot give
        self._step_starts: "collections.deque[float]" = \
            collections.deque(maxlen=257)
        # what the model counted in the last recorded decode step
        # (`ServeModel.step_facts`), for status()
        self._step_facts: Optional[Dict] = None
        self._counts = {k: 0 for k in
                        ("eos", "length", "rejected", "cancelled",
                         "error", "preempted")}
        self.warmed = False
        self.warmstart_adopted = 0
        SLOTS.set(max(self.decode_slots), state="configured")
        if self.config.warmstart:
            self.load_warmstart(self.config.warmstart)

    # -- boot validation (PR 8 shape) ----------------------------------

    def _validate_boot(self):
        """Config + trace findings in the analysis Finding shape. Like
        the serving Engine's boot walk: always runs (boot is one-time),
        raises AnalysisError only at PADDLE_TPU_VALIDATE=2, and lands
        in the analysis metrics under where="decode"."""
        from .. import analysis as _an

        t0 = time.perf_counter()
        findings: List[_an.Finding] = []

        def add(sev, msg, var=None):
            findings.append(_an.Finding(
                severity=sev, pass_name="decode_config", message=msg,
                var=var))

        kv, mc = self.kv_cfg, self._model
        if mc.refusal:
            add(_an.ERROR, mc.refusal)
        if kv.usable_blocks < kv.max_blocks_per_seq:
            add(_an.ERROR,
                f"KV pool cannot hold ONE full sequence: "
                f"{kv.usable_blocks} usable blocks < "
                f"{kv.max_blocks_per_seq} blocks for max_len "
                f"{kv.max_len}", var="num_blocks")
        worst = max(self.decode_slots) * kv.max_blocks_per_seq
        if kv.usable_blocks < worst:
            add(_an.WARNING,
                f"KV pool oversubscribed: {kv.usable_blocks} usable "
                f"blocks < {worst} worst-case ({max(self.decode_slots)} "
                f"slots x {kv.max_blocks_per_seq} blocks) — expect "
                "preemptions under full-length load", var="num_blocks")
        if kv.max_len > mc.max_len:
            add(_an.ERROR,
                f"max_len {kv.max_len} exceeds the positions the model "
                f"addresses ({mc.max_len})", var="max_len")
        if not (-1 <= self.eos_id < mc.vocab_size):
            add(_an.ERROR,
                f"eos_id {self.eos_id} outside vocab [0, "
                f"{mc.vocab_size})", var="eos_id")
        if self.prefill_chunk:
            # the chunked program covers ANY prompt length under
            # max_len, so the bucket-coverage checks (including the
            # "largest prefill bucket < max_len" preemption-replay
            # warning) are retired on this path: preempt replays
            # re-chunk at any length
            if self.prefill_chunk > kv.max_len:
                add(_an.ERROR,
                    f"prefill_chunk {self.prefill_chunk} exceeds "
                    f"max_len {kv.max_len}", var="prefill_chunk")
        else:
            for t in self.prefill_buckets:
                if t > kv.max_len:
                    add(_an.ERROR, f"prefill bucket {t} exceeds max_len "
                        f"{kv.max_len}", var="prefill_buckets")
            if max(self.prefill_buckets) < kv.max_len:
                add(_an.WARNING,
                    f"largest prefill bucket "
                    f"{max(self.prefill_buckets)} < max_len "
                    f"{kv.max_len}: a pool-pressure preemption whose "
                    "replay prompt (original + generated) outgrows the "
                    "bucket set fails that request — extend "
                    "prefill_buckets to max_len if preemptions are "
                    "expected", var="prefill_buckets")
        if self._draft_model is not None:
            dc = self._draft_model
            if dc.vocab_size != mc.vocab_size:
                add(_an.ERROR,
                    f"draft vocab_size {dc.vocab_size} != target "
                    f"{mc.vocab_size}: proposed ids would be "
                    "meaningless to the verifier", var="draft")
            if dc.max_len < kv.max_len:
                add(_an.ERROR,
                    f"draft max_len {dc.max_len} < serving max_len "
                    f"{kv.max_len}: the draft runs every position the "
                    "target does", var="draft")
            if dc.refusal:
                add(_an.ERROR, f"draft model: {dc.refusal}", var="draft")
        if self.spec_k and self.spec_k >= kv.max_len:
            add(_an.ERROR, f"spec_k {self.spec_k} >= max_len "
                f"{kv.max_len}", var="spec_k")
        for s in self.decode_slots:
            if s < 1:
                add(_an.ERROR, f"decode slot count {s} < 1",
                    var="decode_slots")
        if not any(f.severity == _an.ERROR for f in findings):
            # shape-trace every phase executable (no XLA, milliseconds):
            # a shape bug fails boot with a structured finding instead
            # of an opaque trace error inside the first live request
            for key in self._phase_keys():
                try:
                    disp = self._phase_dispatch(key)
                    jax.eval_shape(disp._jit, *self._phase_avals(key))
                except Exception as e:
                    findings.append(_an.Finding(
                        severity=_an.ERROR, pass_name="decode_trace",
                        message=f"{key[0]}@{key[1]} fails to trace: "
                                f"{type(e).__name__}: {str(e)[:200]}"))
        _telemetry.record_analysis(
            findings, n_ops=len(self._phase_keys()),
            where="decode", seconds=time.perf_counter() - t0)
        out = {"errors": 0, "warnings": 0, "infos": 0}
        for f in findings:
            out[f.severity + "s"] = out.get(f.severity + "s", 0) + 1
        if any(f.severity == _an.ERROR for f in findings) \
                and _an.validate_level() >= 2:
            raise _an.AnalysisError(findings)
        return out

    # -- phase grid / warmstart ----------------------------------------

    def _phase_keys(self) -> List[Tuple[str, int]]:
        keys: List[Tuple[str, int]] = []
        if self.prefill_chunk:
            keys.append(("chunk", self.prefill_chunk))
        else:
            keys.extend(("prefill", t) for t in self.prefill_buckets)
        keys.extend(("decode", s) for s in self.decode_slots)
        keys.extend(("assemble", pair) for pair in self._assemble)
        if self._draft is not None:
            if self.prefill_chunk:
                keys.append(("draft_chunk", self.prefill_chunk))
            else:
                keys.extend(("draft_prefill", t)
                            for t in self.prefill_buckets)
            keys.extend(("draft_decode", s) for s in self.decode_slots)
            keys.extend(("verify", s) for s in self.decode_slots)
        return keys

    def _phase_dispatch(self, key) -> _JitDispatch:
        """The grid is a flat (kind, size) → dispatcher map; every
        consumer (boot trace, warmup, warmstart export/load) walks it
        through this one lookup."""
        kind, n = key
        return {"prefill": self._prefill, "chunk": self._chunk,
                "decode": self._decode, "assemble": self._assemble,
                "draft_prefill": self._draft_prefill,
                "draft_chunk": self._draft_chunk,
                "draft_decode": self._draft_decode,
                "verify": self._verify}[kind][n]

    def _phase_avals(self, key):
        sds = jax.ShapeDtypeStruct
        kind, n = key
        if kind == "assemble":
            return (sds((n[0],), np.int32), sds((1,), np.int32),
                    sds((n[1],), np.int32))
        draft = kind.startswith("draft_")
        params = self._draft_params if draft else self.params
        p_sds = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), params)
        kv = self._draft_kv_cfg if draft else self.kv_cfg
        kpool, vpool = (sds(shape, np.dtype(kv.dtype))
                        for shape in kv.pool_shapes)
        mb = kv.max_blocks_per_seq
        base = kind[6:] if draft else kind
        state = tuple(sds(shape, dt) for shape, dt in self._state_specs)
        # a window kind's tables follow the row id(s)
        wkind = self._wkv_cfg is not None
        if base == "prefill":
            return (p_sds, sds((1, n), np.int32), sds((), np.int32),
                    kpool, vpool, sds((mb,), np.int32)) \
                + ((state, sds((), np.int32)) if state else ()) \
                + ((sds((mb,), np.int32),) if wkind else ())
        if base == "chunk":
            return (p_sds, sds((1, n), np.int32), sds((), np.int32),
                    sds((), np.int32), kpool, vpool,
                    sds((mb,), np.int32))
        if base == "verify":
            return (p_sds, sds((n, self.spec_k + 1), np.int32),
                    sds((n,), np.int32), kpool, vpool,
                    sds((n, mb), np.int32))
        return (p_sds, sds((n,), np.int32), sds((n,), np.int32),
                kpool, vpool, sds((n, mb), np.int32)) \
            + ((state, sds((n,), np.int32)) if state else ()) \
            + ((sds((n, mb), np.int32),) if wkind else ())

    def warmup(self) -> int:
        """AOT-compile (or adopt from the persistent compile cache /
        a loaded warmstart artifact) every phase-grid executable.
        Returns how many phases are ready — all of them: the grid is
        closed and the engine's own, so a phase that does not compile
        raises here, at boot, with the compiler's error, instead of
        failing its first request. Idempotent."""
        keys = self._phase_keys()
        with _tracing.boot_span("boot.engine_warmup") as boot:
            boot["phases"] = len(keys)
            for key in keys:
                disp = self._phase_dispatch(key)
                with _tracing.boot_span("boot.warm_phase") as phase:
                    phase["phase"] = _phase_name(key)
                    ok = disp.warm(*self._phase_avals(key))
                    # how the executable got there ("compiled",
                    # "jax_cache", "paddle_cache", "warmstart",
                    # "remembered"), None where none did: read by
                    # `status()["boot"]["phases"]`
                    phase["installed"] = disp.installed
                if not ok:
                    raise RuntimeError(
                        f"decode phase {key[0]}@{key[1]} failed to "
                        f"compile") from disp.aot_error
        self.warmed = True
        return len(keys)

    def _model_digest(self) -> str:
        """Binds warmstart artifacts to THIS model + grid: params
        content, model config, and the kv/pool geometry that shapes
        every executable."""
        h = hashlib.sha256()
        h.update(repr((self.model_cfg, self.kv_cfg,
                       self.decode_slots,
                       self.prefill_buckets,
                       self.config.precision,
                       self.eos_id,
                       self.prefill_chunk, self.spec_k,
                       self._draft_cfg)).encode())
        for name in sorted(self.params):
            a = np.ascontiguousarray(np.asarray(self.params[name]))
            h.update(f"{name}:{a.dtype}:{a.shape}".encode())
            h.update(a.tobytes())
        if self._draft_params is not None:
            for name in sorted(self._draft_params):
                a = np.ascontiguousarray(
                    np.asarray(self._draft_params[name]))
                h.update(f"draft:{name}:{a.dtype}:{a.shape}".encode())
                h.update(a.tobytes())
        return h.hexdigest()

    def export_warmstart(self, path: str) -> int:
        """Serialize every warmed phase executable into ONE artifact
        (the PR 6 pattern, keyed by phase instead of batch bucket).
        Call after warmup(); returns how many phases it carries."""
        entries = {}
        for key in self._phase_keys():
            disp = self._phase_dispatch(key)
            exe = disp._aot
            if exe is None:
                continue
            try:
                avals = self._phase_avals(key)
                fp = disp.cache_fingerprint(disp.lower(*avals))
                entries[key] = {
                    "blob": _cc.serialize_executable(exe),
                    "fingerprint": fp}
            except Exception:
                continue  # backend refused: artifact covers fewer phases
        grid = {"decode": list(self.decode_slots)}
        if self.prefill_chunk:
            # chunked path: the bucket dimension is collapsed, so the
            # artifact advertises the chunk size, not buckets
            grid["chunk"] = self.prefill_chunk
        else:
            grid["prefill"] = list(self.prefill_buckets)
        if self.spec_k:
            grid["spec_k"] = self.spec_k
        art = dict(_cc.environment_meta(),
                   format=DECODE_WARMSTART_FORMAT,
                   model_digest=self._model_digest(),
                   grid=grid,
                   created_at=time.time(),
                   entries=entries)
        from ..resilience.atomic import write_bytes

        write_bytes(path, pickle.dumps(art,
                                       protocol=pickle.HIGHEST_PROTOCOL))
        _events.emit("warmstart", action="export_decode", path=path,
                     entries=len(entries))
        return len(entries)

    def load_warmstart(self, path: str) -> int:
        """Adopt the phase executables from a decode warmstart
        artifact; same degradation contract as the serving engine's:
        any mismatch (environment, model digest, per-entry lowering
        fingerprint) costs a reject event + a cold phase, never a
        boot failure."""
        try:
            with open(path, "rb") as f:
                art = pickle.loads(f.read())
            if not isinstance(art, dict) or \
                    art.get("format") != DECODE_WARMSTART_FORMAT:
                raise ValueError("not a decode warmstart artifact")
        except Exception as e:
            _events.emit("warmstart", action="reject", path=path,
                         reason=f"unreadable: {str(e)[:200]}")
            self.warmstart_adopted = 0
            return 0
        env = _cc.environment_meta()
        stored = {k: art.get(k) for k in env}
        if stored != env:
            _events.emit("warmstart", action="reject", path=path,
                         reason=f"environment mismatch: artifact "
                                f"{stored} vs process {env}")
            self.warmstart_adopted = 0
            return 0
        if art.get("model_digest") != self._model_digest():
            _events.emit("warmstart", action="reject", path=path,
                         reason="model digest mismatch — artifact baked "
                                "from a different model/grid")
            self.warmstart_adopted = 0
            return 0
        adopted = 0
        for key, entry in (art.get("entries") or {}).items():
            try:
                kind, n = key
                try:
                    disp = self._phase_dispatch((kind, n))
                except KeyError:
                    continue  # artifact baked with a different grid
                avals = self._phase_avals((kind, n))
                fp = disp.cache_fingerprint(disp.lower(*avals))
                if fp is None or fp != entry["fingerprint"]:
                    continue  # lowering/flags drifted since the bake
                exe = _cc.deserialize_executable(entry["blob"])
                disp.adopt(exe, *avals)
                adopted += 1
            except Exception:
                continue
        self.warmstart_adopted = adopted
        _events.emit("warmstart", action="load_decode", path=path,
                     adopted=adopted)
        return adopted

    # -- client API ----------------------------------------------------

    def start(self):
        """Start the scheduler thread (idempotent; submit() calls it)."""
        with self._cv:
            if self._thread is not None or self._closed:
                return
            self._thread = threading.Thread(
                target=self._loop_sync if self._sync else self._loop,
                name="paddle-tpu-decode", daemon=True)
            self._thread.start()
            _events.emit("decode", action="start",
                         slots=list(self.decode_slots),
                         prefill_buckets=list(self.prefill_buckets),
                         blocks=self.kv_cfg.usable_blocks)

    def submit(self, prompt_ids, max_new_tokens: int = 16,
               tenant: Optional[str] = None) -> DecodeHandle:
        """Enqueue one generation; returns its token-stream handle.
        Reject-not-block: QueueFullError (HTTP 503) when max_queue
        prompts already wait, ServerClosed after stop(). Under a QoS
        policy (DecodeConfig(qos=...)) a full queue sheds the lowest-
        tier waiter (newest first within the tier) via qos.ShedError —
        possibly a QUEUED victim, in which case this arrival is
        admitted in its place — and per-tenant quotas bound one
        tenant's waiting+active footprint."""
        prompt = np.asarray(prompt_ids, np.int32).ravel()
        if prompt.size < 1:
            raise ValueError("prompt must carry at least one token id")
        if self.prefill_chunk:
            # chunked prefill has no bucket ceiling: any prompt that
            # leaves generation room under max_len is admissible
            if prompt.size > self.kv_cfg.max_len - 1:
                raise ValueError(
                    f"prompt length {prompt.size} leaves no room to "
                    f"generate under max_len {self.kv_cfg.max_len}")
        elif prompt.size > self.prefill_buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest "
                f"prefill bucket {self.prefill_buckets[-1]}")
        if int(prompt.min()) < 0 or \
                int(prompt.max()) >= self._model.vocab_size:
            raise ValueError(
                f"prompt token ids must be in [0, "
                f"{self._model.vocab_size})")
        room = self.kv_cfg.max_len - int(prompt.size)
        if room < 1:
            raise ValueError(
                f"prompt length {prompt.size} leaves no room to "
                f"generate under max_len {self.kv_cfg.max_len}")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        max_new = min(int(max_new_tokens), room)
        tenant = str(tenant) if tenant else self._qosm.DEFAULT_TENANT
        shed_victim: Optional[_Request] = None
        shed_err: Optional[BaseException] = None
        with self._cv:
            if self._closed:
                self._count("rejected", tenant)
                raise ServerClosed("decode engine is stopped")
            if self._draining:
                self._count("rejected", tenant)
                raise ServerClosed(
                    "decode engine is draining; request rejected")
            qos = self._qos
            if qos is not None:
                quota = qos.quota_of(tenant)
                if quota is not None:
                    have = sum(1 for r in self._waiting
                               if r.tenant == tenant) \
                        + sum(1 for r in self._active
                              if r.tenant == tenant) \
                        + sum(1 for r in self._prefilling
                              if r.tenant == tenant)
                    if have >= quota:
                        tier = qos.tier_of(tenant)
                        self._qosm.SHEDS.inc(tier=tier, kind="quota")
                        _events.emit("shed", where="decode",
                                     tenant=tenant, tier=tier,
                                     shed="quota")
                        self._count("rejected", tenant)
                        raise self._qosm.ShedError(
                            f"tenant {tenant!r} over quota ({quota} "
                            "concurrent generations); request rejected",
                            tenant=tenant, tier=tier, kind="quota")
            if len(self._waiting) >= self.config.max_queue:
                if qos is None:
                    self._count("rejected", tenant)
                    raise QueueFullError(
                        f"decode queue full ({self.config.max_queue} "
                        "waiting); request rejected")
                # tier-ordered shed: lowest tier first, newest first
                # within the tier, the arrival included as a candidate
                entries = [(r.tenant, r.rid) for r in self._waiting] \
                    + [(tenant, self._rid + 1)]
                vi = self._qosm.shed_victim(entries, qos)
                v_tenant = entries[vi][0]
                v_tier = qos.tier_of(v_tenant)
                self._qosm.SHEDS.inc(tier=v_tier, kind="queue")
                _events.emit("shed", where="decode", tenant=v_tenant,
                             tier=v_tier, shed="queue")
                err = self._qosm.ShedError(
                    f"decode queue full ({self.config.max_queue} "
                    f"waiting); shed tier {v_tier!r} (tenant "
                    f"{v_tenant!r})",
                    tenant=v_tenant, tier=v_tier, kind="queue")
                if vi == len(entries) - 1:   # the arrival is the victim
                    self._count("rejected", tenant)
                    raise err
                shed_victim = self._waiting[vi]
                del self._waiting[vi]
                shed_err = err
            self._rid += 1
            req = _Request(self._rid, prompt, max_new, tenant)
            self._waiting.append(req)
            QUEUE_DEPTH.set(len(self._waiting))
            self._cv.notify_all()
        if shed_victim is not None:
            # outside the lock (matches _sweep_cancelled's finish
            # discipline): end the victim's stream with the typed error
            shed_victim.error = shed_err
            self._count("rejected", shed_victim.tenant)
            shed_victim.finish_reason = "rejected"
            shed_victim.events.put(None)
        self.start()
        return DecodeHandle(req)

    def cancel(self, handle: DecodeHandle):
        """Abandon one generation (the HTTP frontend calls this when a
        streaming client disconnects): the scheduler retires the
        request at its next iteration, freeing its slot and KV blocks
        instead of generating the full max_new_tokens into an unread
        queue. Idempotent; a no-op once the request finished."""
        with self._cv:
            handle._req.cancelled = True
            self._cv.notify_all()

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful drain (the fleet's scale-in path, SERVING.md
        §Fleet): stop admitting — new submits raise ServerClosed/503 —
        but let every waiting and active generation run to completion.
        Returns True when the engine emptied within `timeout_s` (False:
        caller decides whether to stop() anyway, cancelling the rest).
        Idempotent; a later start of new traffic requires a new engine.
        """
        deadline = time.monotonic() + float(timeout_s)
        with self._cv:
            if not self._draining:
                self._draining = True
                _events.emit("decode", action="drain",
                             waiting=len(self._waiting),
                             active=len(self._active))
        while time.monotonic() < deadline:
            with self._cv:
                if self._closed or (not self._waiting
                                    and not self._active
                                    and not self._prefilling):
                    return True
            time.sleep(0.01)
        with self._cv:
            return not self._waiting and not self._active \
                and not self._prefilling

    def stop(self):
        """Stop the scheduler: waiting and active requests are
        cancelled (their streams end with finish_reason='cancelled').
        Idempotent; joins the thread. Requests enqueued before any
        scheduler thread existed are drained HERE — _loop's finally
        (the usual cleanup) never runs for a thread never started, and
        a submit racing this stop must not strand its caller blocking
        on a stream that nothing will ever terminate."""
        with self._cv:
            if not self._closed:
                self._closed = True
                self._cv.notify_all()
            t = self._thread
            stranded = [] if t is not None else list(self._waiting)
            if t is None and stranded:
                self._waiting.clear()
                QUEUE_DEPTH.set(0)
        for req in stranded:
            self._finish(req, "cancelled")
        if t is not None:
            t.join(timeout=30.0)
        for h in getattr(self, "_mem_handles", ()):
            _memwatch.unregister_provider(h)
        self._mem_handles = []
        _events.emit("decode", action="stop")

    def load(self) -> Tuple[int, int]:
        """(queued, active) — the cheap pair the /v1/load probe folds
        into its scalar load score without building the full status
        document."""
        with self._cv:
            return (len(self._waiting),
                    len(self._active) + len(self._prefilling))

    def status(self) -> Dict:
        with self._cv:
            waiting = len(self._waiting)
            active = len(self._active)
            prefilling = len(self._prefilling)
            live_tokens = sum(r.pos for r in self._active)
            live_tokens += sum(r.prefill_pos for r in self._prefilling)
            window_tokens = self._window_tokens(self._active)
            counts = dict(self._counts)
            draining = self._draining
        grid = {"decode_slots": list(self.decode_slots)}
        if self.prefill_chunk:
            grid["prefill_chunk"] = self.prefill_chunk
        else:
            grid["prefill_buckets"] = list(self.prefill_buckets)
        out = {
            "draining": draining,
            "phase_grid": grid,
            "queue_depth": waiting,
            "active": active,
            "slot_config": self._last_slot_config,
            "precision": self.config.precision,
            "eos_id": self.eos_id,
            "warmed": self.warmed,
            "warmstart_adopted": self.warmstart_adopted,
            "analysis": self.analysis,
            "kv": self._kv_status(live_tokens, window_tokens),
            "requests": counts,
            "step_ms": self._step_ms(),
            "step_facts": self._step_facts,
            # what the model says of itself beyond the cache's shape
            # (`ServeModel.describe`: residual streams, the rounds of a
            # projection its maps go through); {} for most
            "model": self._model.describe(),
            # which route decode attention took, a count a traced decode
            # program of this process ("paged", "paged_latent": a kernel
            # over the live blocks; "gather": the padded gather), and the
            # tokens a chunk of a walk that has a number of its own
            # ("paged_sparse_chunk_tokens")
            "decode_attention": _paged_attention.gate_report(),
            # which route a whole prompt's attention took, a count a traced
            # `mha` call ("causal": the causal prompt kernel; "splash";
            # "xla"); {} for a model whose prompts do not go through `mha`
            "prompt_attention": dict(_attention.GATE_COUNTS),
            # which route the expert layers' grouped matmuls took, a count
            # a traced call ("megablox": the kernel; "xla": `ragged_dot`),
            # and the kernel's tiles by the matrix it read, which
            # `grouped_matmul.tiles` chose from the shapes:
            # {"2688x1920": [128, 896, 1920]}; both {} for a dense model
            "expert_matmul": {
                "routes": dict(_grouped_matmul.GATE_COUNTS),
                "tiles": {f"{k}x{n}": list(t) for (k, n), t in
                          sorted(_grouped_matmul.TILES.items())}},
            # which unit whole-prompt writes into the pool took, a count a
            # traced write, two (K and V) a prefill program ("blocks": a
            # bucket of whole blocks goes in a block at a time; "rows": a
            # bucket that is not, a token at a time)
            "prefill_write": dict(PREFILL_WRITE_UNITS),
            # what this PROCESS spent before it served, computed now from
            # the kept boot spans and compile-request rows: seconds from
            # the process's start to the last warm-up's end, by boot span
            # and by warm-up phase (with where its executable came from),
            # and the compile requests' count, cache answers, tracing /
            # lowering / backend sums and slowest programs
            "boot": _boot_status(),
        }
        if self._state_alloc is not None:
            # the state row pools beside the K/V pools: rows a sequence can
            # hold, rows held, device bytes
            out["state"] = self._state_alloc.stats()
            # which route the decode programs' state updates took, a count
            # a recurrent layer traced ("kernel": the rows advanced in
            # place, ops/pallas/ssm_update.py; "xla": gathered, advanced
            # and scattered back)
            out["state"]["update"] = dict(_ssm_update.GATE_COUNTS)
        if self._qos is not None:
            out["qos"] = {
                "policy": self._qos.spec_dict(),
                "served_shares": {
                    t: round(s, 4) for t, s in
                    self._wfq.served_shares().items()},
            }
        if not self._sync:
            # the lazy loop's queue on the device: decode steps by how
            # they got their ids ("fed": the step in flight's tokens as
            # they are; "assembled": put together on the device after an
            # admission or a retirement; "host": built from host tokens,
            # nothing in flight), the forced resolves of everything in
            # flight (pool exhaustion, a request's only token, shutdown)
            # and, counted while a recording is on, the dispatches before
            # which the host saw the device's queue empty ("starved")
            out["pipeline"] = dict(self._pipeline)
        else:
            out["prefilling"] = prefilling
            out["kv_reuse"] = {
                "prefix_cache": self.config.prefix_cache,
                "prefill_chunk": self.prefill_chunk,
                "spec_k": self.spec_k,
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "spec_accept_rate": round(
                    self._spec_accepted / self._spec_proposed, 4)
                if self._spec_proposed else None,
            }
        return out

    def _window_tokens(self, reqs) -> int:
        """Keys the window layers hold for `reqs`: the newest `window` of
        each, or its length; 0 for a model of one cache kind."""
        w = self._model.window
        return sum(min(r.pos, w) for r in reqs if r is not None) if w else 0

    def _kv_status(self, live_tokens: int, window_tokens: int) -> Dict:
        """`status()["kv"]`: the global kind's allocator as ever, and for a
        model with a window kind `kinds` (each kind's own numbers; the
        window's `ring_blocks` and `window` beside them) with `pool_bytes`
        counting every pool."""
        kv = self._alloc.stats(live_tokens=live_tokens)
        if self._walloc is None:
            return kv
        own = ("blocks_total", "blocks_free", "blocks_used", "live_tokens",
               "allocated_token_capacity", "run_chunk_share",
               "walk_chunk_tokens", "pool_bytes", "bytes_per_token_layer")
        window = self._walloc.stats(live_tokens=window_tokens)
        kv["kinds"] = {
            "global": dict({k: kv[k] for k in own},
                           layers=self.kv_cfg.layers),
            "window": dict({k: window[k] for k in own},
                           layers=self._wkv_cfg.layers,
                           window=int(self._model.window),
                           ring_blocks=self._ring)}
        kv["pool_bytes"] += window["pool_bytes"]
        return kv

    def _step_ms(self) -> Optional[Dict]:
        """Start-to-start times of the last (up to 256) decode steps:
        an admission's prefill lies inside the gap it delays, so the
        median is the bare step and the p95 a step plus a prefill."""
        starts = list(self._step_starts)
        gaps = sorted(b - a for a, b in zip(starts, starts[1:]))
        if not gaps:
            return None
        return {"n": len(gaps),
                "p50": round(1e3 * gaps[len(gaps) // 2], 3),
                "p95": round(1e3 * gaps[min(len(gaps) - 1,
                                            int(0.95 * len(gaps)))], 3)}

    # -- scheduler internals (single thread owns everything below) -----

    def _count(self, outcome: str, tenant: Optional[str] = None):
        REQUESTS.inc(outcome=outcome)
        if self._qos is not None and tenant is not None:
            self._qosm.TENANT_REQUESTS.inc(
                tenant=tenant, tier=self._qos.tier_of(tenant),
                outcome=outcome)
        self._counts[outcome] = self._counts.get(outcome, 0) + 1

    def _to_reader(self, req: _Request, item) -> None:
        """A token (or the stream's end) on its way to the request's
        reader. The lazy loop keeps what a turn resolves in `_outbox` and
        hands it over AFTER the next dispatch (`_flush_outbox`): a `put`
        wakes the reader's thread, and 128 readers awake while the loop
        prepares the next step take the interpreter lock from it every
        time a call into JAX or numpy lets go of it, 9 of a turn's 22 ms at
        128 slots, with the device idle a tenth of the window (chip runs of
        PR 49). Handed over just before the loop waits for the step in
        flight, the readers write while it waits. Outside the lazy loop's
        thread (a synchronous loop, `stop()` before a start) there is no
        outbox and the item goes at once."""
        if self._outbox is None:
            self._hand_over(req, item)
        else:
            self._outbox.append((req, item))

    def _flush_outbox(self) -> None:
        box, self._outbox = self._outbox, []
        sp = _tracing.open_span("decode.flush", "decode") \
            if _tracing.recording and box else None
        for req, item in box:
            self._hand_over(req, item)
        if sp is not None:
            sp.close(items=len(box))

    def _hand_over(self, req: _Request, item) -> None:
        if item is not None and req.t_first is None:
            self._first_token_out(req)
        req.events.put(item)

    def _first_token_out(self, req: _Request) -> None:
        """The request's first token leaves for its reader NOW: the time
        to the first token ends here, the outbox's hold inside it, and not
        where the token was resolved."""
        req.t_first = time.monotonic()
        TTFT_SECONDS.observe(req.t_first - req.t_submit)
        if self._qos is not None:
            self._qosm.TENANT_TTFT_SECONDS.observe(
                req.t_first - req.t_submit, tenant=req.tenant)
        if _tracing.recording or req.traced:
            # per-request TTFT span: submit -> first token handed over
            _tracing.record(
                "decode.ttft", req.t_submit, req.t_first, "decode",
                parent=req.parent, rid=req.rid, ctx=req.tctx,
                prompt_len=req.prompt_len0, tenant=req.tenant)

    def _emit_token(self, req: _Request, tok: int, phase: str):
        req.last_token = int(tok)
        req.generated.append(int(tok))
        TOKENS.inc(phase=phase)
        if self._wfq is not None:
            # token-granular service charge: the admission pick reads
            # these virtual times, so sustained token flow to one
            # tenant defers its next admission in favor of underserved
            # same-tier tenants
            self._wfq.charge(req.tenant, 1)
            self._qosm.TENANT_TOKENS.inc(tenant=req.tenant)
        self._to_reader(req, int(tok))

    def _finished_reason(self, req: _Request) -> Optional[str]:
        if req.generated and req.generated[-1] == self.eos_id:
            return "eos"
        if len(req.generated) >= req.max_new:
            return "length"
        return None

    def _finish(self, req: _Request, reason: str):
        req.finish_reason = reason
        if req.t_first is None and req.generated:
            # its first token still waits in the outbox and leaves with
            # the stream's end
            self._first_token_out(req)
        if _tracing.recording or req.traced:
            self._record_finish(req, reason, time.monotonic())
        if req.blocks:
            self._alloc.free(req.blocks)   # reuse allocator: decref;
            req.blocks = []                # cached blocks go to LRU
        self._free_state_row(req)
        self._free_ring(req)
        if req in self._active:
            self._active.remove(req)
        if req in self._prefilling:
            self._prefilling.remove(req)
        self._count(reason, req.tenant)
        self._to_reader(req, None)
        self._kv_gauges()

    def _free_state_row(self, req: _Request) -> None:
        """A sequence that leaves (finish, cancel, preemption) gives its
        state row back; what the row holds is overwritten by the prefill
        of whoever takes it next, which the device runs after every step
        already dispatched with this sequence in it."""
        if req.state_row != NULL_ROW:
            self._state_alloc.free(req.state_row)
            req.state_row = NULL_ROW

    def _free_ring(self, req: _Request) -> None:
        """A sequence that leaves gives its window-kind blocks back, as its
        state row: whoever takes them writes every key before it reads it
        (a prefill's slices, then a token a step), after every step already
        dispatched with this sequence in it."""
        if req.wblocks:
            self._walloc.free(req.wblocks)
            req.wblocks = []

    def _record_finish(self, req: _Request, reason: str, now: float):
        if req.t_first is not None and len(req.generated) > 1:
            # decode-phase span: first token -> last token (the
            # prefill/TTFT spans cover everything before it)
            _tracing.record(
                "decode.decode", req.t_first, now, "decode",
                parent=req.parent, rid=req.rid, ctx=req.tctx,
                tokens=len(req.generated) - 1)
        _tracing.record(
            "decode.generate", req.t_submit, now, "decode",
            parent=req.parent, rid=req.rid, ctx=req.tctx,
            tokens=len(req.generated), reason=reason, tenant=req.tenant)
        if _tracing.recording:
            # the request record: what a reader needs of _Request
            # without reaching into it (times are CLOCK_MONOTONIC)
            _tracing.add_record("decode.requests", {
                "rid": req.rid,
                "trace_id": req.tctx.trace_id if req.tctx else None,
                "arrival": req.arrival if req.arrival is not None
                else req.t_submit,
                "t_submit": req.t_submit,
                "enqueued_at": req.enqueued_at,
                "admitted_at": req.admitted_at or None,
                "t_first": req.t_first, "t_finish": now,
                "prompt_len": req.prompt_len0,
                "n_tokens": len(req.generated), "outcome": reason,
                "preemptions": req.preempted, "tenant": req.tenant})

    def _step_record(self, kind: str, t: float, slots: int, live: int,
                     live_tokens: int, window_tokens: int = 0) -> Dict:
        """One row per dispatched program (recording on): what ran, how
        full it was, and the allocator's own count of blocks (`blocks_*`
        and `live_tokens` are the global kind's; a model with a window kind
        has `window_*` beside them)."""
        row = {"t": t, "kind": kind, "slots": slots, "live": live,
               "live_tokens": live_tokens,
               "blocks_used": self._alloc.used_blocks(),
               "blocks_usable": self.kv_cfg.usable_blocks}
        if self._walloc is not None:
            row["window_blocks_used"] = self._walloc.used_blocks()
            row["window_blocks_usable"] = self._wkv_cfg.usable_blocks
            row["window_tokens"] = window_tokens
        if self._state_alloc is not None:
            row["state_rows_used"] = self._state_alloc.used_rows()
            row["state_rows"] = self._state_alloc.rows - 1
        _tracing.add_record("decode.steps", row)
        return row

    def _note_step_stats(self, row: Dict, stats) -> None:
        """Recording on: what the model counted in the step that `row`
        records (an expert layer's `experts_hit`, `expert_load_max`)
        joins the row, fetched once the step's tokens are on the host;
        `status()["step_facts"]` stays the newest DECODE step's."""
        facts = self._model.step_facts(jax.device_get(stats))
        if row["kind"] == "decode":
            self._step_facts = facts
        row.update(facts)

    def _kv_gauges(self):
        KV_BLOCKS.set(self._alloc.used_blocks(), state="used")
        KV_BLOCKS.set(self._alloc.free_blocks(), state="free")
        if self.config.prefix_cache:
            KV_BLOCKS.set(self._alloc.cached_blocks(), state="cached")
        SLOTS.set(len(self._active), state="active")

    def _bucket_for_len(self, n: int) -> Optional[int]:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return None

    def _slot_config(self) -> int:
        n = max(1, len(self._active))
        for s in self.decode_slots:
            if n <= s:
                return s
        return self.decode_slots[-1]

    def _queue_empty(self) -> Optional[float]:
        """Recording on, before a span's first enqueuing call (at the
        opening of `decode.dispatch` and again where its build ends, at
        the opening of an admission's `decode.prefill`): the time on
        `tracing.clock` if the host sees the device's queue EMPTY (the
        newest entry in flight has its tokens ready; nothing in flight
        counts as ready), else None. From here to that call's return the
        device has nothing to run: the span's `starved_s`, a lower bound
        on the device's idle time that needs no profiler."""
        if self._inflight and not self._inflight[-1].tok_dev.is_ready():
            return None
        self._pipeline["starved"] += 1
        return _tracing.clock()

    def _same_bucket_waiting(self, req: _Request) -> int:
        """Recording on, as `req` is admitted: how many requests still
        waiting fall into its prefill bucket (within 256 tokens of its
        length where no bucket holds it): the company a batched prefill
        would have found."""
        n = len(req.prompt)
        bucket = self._bucket_for_len(n)
        with self._cv:
            if bucket is None:
                return sum(abs(len(r.prompt) - n) <= 256
                           for r in self._waiting)
            return sum(self._bucket_for_len(len(r.prompt)) == bucket
                       for r in self._waiting)

    def _sweep_cancelled(self):
        """Retire requests whose clients abandoned them (cancel()):
        waiting ones leave the queue, active ones free their slot and
        blocks. Runs at the top of every scheduler iteration; a
        cancelled request with a token still in flight (a step's, or its
        own prefill's first) is skipped by the resolve's not-in-active
        check."""
        with self._cv:
            gone_waiting = [r for r in self._waiting if r.cancelled]
            for r in gone_waiting:
                self._waiting.remove(r)
            if gone_waiting:
                QUEUE_DEPTH.set(len(self._waiting))
        for r in gone_waiting:
            self._finish(r, "cancelled")
        for r in [r for r in self._active if r.cancelled]:
            self._finish(r, "cancelled")
        for r in [r for r in self._prefilling if r.cancelled]:
            self._finish(r, "cancelled")

    def _pick_waiting_locked(self) -> int:
        """Index of the next waiting request to admit (caller holds
        _cv, _waiting non-empty): FIFO without a QoS policy; (tier
        priority, weighted-fair virtual time) with one."""
        if self._wfq is None:
            return 0
        return self._wfq.pick([r.tenant for r in self._waiting])

    def _victim_key(self, r: _Request):
        """Preemption/shed ordering under KV pressure: lowest tier
        first (max tier rank), youngest admission within the tier —
        identical to the historical youngest-first rule when no QoS
        policy is attached (rank is constant 0)."""
        rank = 0 if self._qos is None else self._qos.rank_of(r.tenant)
        return (rank, r.admitted_at)

    def _admit(self) -> bool:
        """Move waiting requests into free slots while blocks last. Each
        admission dispatches its prefill and joins `_active` at once; its
        first token stays on the device, behind whatever is in flight,
        and is emitted when `_resolve` reaches it. Only a request whose
        first token is its last (it joins no decode batch) is resolved
        here, with everything queued before it. Returns whether the batch
        composition changed."""
        admitted = 0
        max_slots = self.decode_slots[-1]
        waiting = len(self._waiting)
        sp = _tracing.open_span("decode.admit", "decode") \
            if _tracing.recording and waiting else None
        while True:
            with self._cv:
                if not self._waiting or self._closed:
                    break
                if len(self._active) >= max_slots:
                    break
                idx = self._pick_waiting_locked()
                req = self._waiting[idx]
                need = -(-len(req.prompt) // self.kv_cfg.block_size)
                if not self._alloc.can_alloc(need) or (
                        self._walloc is not None and not
                        self._walloc.can_alloc(min(need, self._ring))):
                    break  # blocks scale with live tokens: defer
                del self._waiting[idx]
                QUEUE_DEPTH.set(len(self._waiting))
            first = self._prefill_one(req)
            admitted += 1
            if first is not None:
                self._inflight.append(first)
                if len(req.generated) + 1 >= req.max_new:
                    self._drain()
        if sp is not None:
            sp.close(waiting=waiting, admitted=admitted)
        return bool(admitted)

    def _prefill_one(self, req: _Request) -> Optional[_Pending]:
        """Admit `req`: dispatch its whole-prompt prefill and return the
        fetch of its first token, still in flight (None: the replay
        outgrew the bucket set and the request ended in error). The lazy
        loop queues the fetch, the sync loop resolves it at once."""
        # the admission boundary: everything since (re-)enqueue was wait
        req.admitted_at = time.monotonic()
        sp = facts = probe = called = None
        if _tracing.recording or req.traced:
            _tracing.record(
                "decode.queue_wait", req.enqueued_at, req.admitted_at,
                "decode", parent=req.parent, rid=req.rid, ctx=req.tctx,
                tenant=req.tenant)
            sp = _tracing.open_span("decode.prefill", "decode",
                                    parent=req.parent, rid=req.rid,
                                    ctx=req.tctx)
            if _tracing.recording:
                probe = self._queue_empty()
                facts = {"same_bucket_waiting":
                         self._same_bucket_waiting(req),
                         "queue_empty": False}
        bucket = self._bucket_for_len(len(req.prompt))
        try:
            first, called = self._prefill_admitted(req, bucket)
            return first
        finally:
            if sp is not None:
                if probe is not None and called is not None:
                    facts.update(queue_empty=True, starved_s=called - probe)
                # the admitted table's chunks, and how many of them the
                # decode kernels will read with one copy a pool
                runs, chunks = run_chunks(req.blocks, self._alloc.per_chunk)
                sp.close(bucket=bucket, prompt_len=len(req.prompt),
                         queue_wait_s=req.admitted_at - req.enqueued_at,
                         runs=runs, chunks=chunks, **(facts or {}))

    def _prefill_admitted(self, req: _Request, bucket: Optional[int]
                          ) -> Tuple[Optional[_Pending], Optional[float]]:
        """The prompt work of one admitted request: the prefill program
        is dispatched, the request takes its slot, and nothing waits for
        the device (`decode.prefill.wait` holds the call alone). Returns
        the fetch and, recording on, when the call returned on
        `tracing.clock` (the end of the admission's `starved_s`)."""
        if self._wfq is not None:
            # prefill service charge: a long prompt is real work even
            # before its first decode token
            self._wfq.charge(req.tenant, len(req.prompt))
        plen = len(req.prompt)
        if bucket is None:  # replay grew past the largest bucket
            req.error = RuntimeError(
                f"prompt+generated length {plen} exceeds the largest "
                f"prefill bucket {self.prefill_buckets[-1]}")
            self._finish(req, "error")
            return None, None
        need = -(-plen // self.kv_cfg.block_size)
        req.blocks = self._alloc.alloc(need)
        bt = build_block_table(req.blocks, self.kv_cfg.max_blocks_per_seq)
        ids = np.empty((1, bucket), np.int32)
        ids[0, :plen] = req.prompt
        ids[0, plen:] = req.prompt[-1]         # edge-pad (in-distribution)
        kp, vp = self._pools
        state = ()
        if self._state_alloc is not None:
            # a row of its own, which this prefill overwrites from a zero
            # state: nothing of the row's last holder is read
            req.state_row = self._state_alloc.alloc()
        if self._state_specs:
            state = (self._state, np.int32(req.state_row))
        if self._walloc is not None:
            # its ring, or its own length where that is shorter: one run
            # where the free extents hold one
            req.wblocks = self._walloc.alloc(min(need, self._ring))
            state += (window_table(req.wblocks, self._ring,
                                   self.kv_cfg.max_blocks_per_seq),)
        t0 = time.perf_counter()
        wait = row = called = None
        if _tracing.recording:
            row = self._step_record("prefill", t0, 1, 1, plen,
                                    min(plen, self._model.window or 0))
            wait = _tracing.open_span("decode.prefill.wait", "decode")
        tok, kp, vp, *out = self._prefill[bucket](
            self.params, ids, np.int32(plen), kp, vp, bt, *state)
        self._pools = (kp, vp)
        # what the model counted over the bucket's rows, where it counts
        # its prompts (`ServeModel.prefill_counters`)
        stats = out.pop(0) if self._model.prefill_counters else None
        if out:
            self._state = out[0]
        if wait is not None:
            called = wait.close(call_s=time.perf_counter() - t0)
        STEPS.inc(phase="prefill")
        if self._draft is not None:
            # the draft prefills EVERY sequence (same ids, same block
            # table, its own pools) so speculation can start at the
            # first decode round
            dkp, dvp = self._draft_pools
            _, dkp, dvp = self._draft_prefill[bucket](
                self._draft_params, ids, np.int32(plen), dkp, dvp, bt)
            self._draft_pools = (dkp, dvp)
            req.draft_pos = plen
            STEPS.inc(phase="draft")
        req.pos = plen
        self._active.append(req)
        self._kv_gauges()
        pending = _Pending(tok, None, [req], t0)
        if row is not None and stats is not None:
            for a in jax.tree_util.tree_leaves(stats):
                a.copy_to_host_async()
            pending.stats = (row, stats)
        return pending, called

    def _grow_blocks(self) -> None:
        """Ensure every active slot owns the block its next write
        lands in. On pool exhaustion: resolve everything in flight (a
        forced drain: its finishes may free blocks, and a preempted
        request's replay prompt needs every token it was given, a first
        token still on the device included), retry, then preempt the
        youngest active sequence until the step fits."""
        sp = _tracing.open_span("decode.grow", "decode") \
            if _tracing.recording else None
        taken = preempted = 0
        while True:
            short = None
            for req in self._active:
                bi = req.pos // self.kv_cfg.block_size
                try:
                    while bi >= len(req.blocks):
                        self._alloc.grow(req.blocks)
                        taken += 1
                    # the window kind: up to its ring, and none after
                    while len(req.wblocks) <= min(bi, self._ring - 1):
                        self._walloc.grow(req.wblocks)
                        taken += 1
                except NoBlocksError:
                    short = req
                    break
            if short is None:
                break
            if self._inflight:
                self._drain()
                continue  # finishes may have freed enough
            victim = max(self._active, key=self._victim_key)
            self._preempt(victim)
            preempted += 1
        if sp is not None:
            sp.close(blocks=taken, preempted=preempted)

    def _preempt(self, req: _Request):
        """vLLM-style recompute preemption: free the victim's blocks
        and requeue it (front) with prompt = original + generated; the
        replay prefill regenerates its KV and its NEXT token — tokens
        already streamed are not re-emitted."""
        if req in self._active:
            self._active.remove(req)
        else:
            self._prefilling.remove(req)
        self._alloc.free(req.blocks)   # reuse allocator: decref — a
        req.blocks = []                # shared prefix survives for the
        req.prefill_pos = 0            # replay to hit again
        self._free_state_row(req)      # the replay's prefill rebuilds it
        self._free_ring(req)
        req.draft_pos = 0
        req.n_reused = 0
        req.hashes = None
        # replay prompt: original prompt + everything generated so far
        req.prompt = np.concatenate(
            [req.prompt[:req.prompt_len0],
             np.asarray(req.generated, np.int32)])
        req.enqueued_at = time.monotonic()
        req.preempted += 1
        with self._cv:
            self._waiting.appendleft(req)
            QUEUE_DEPTH.set(len(self._waiting))
        PREEMPTIONS.inc()
        self._counts["preempted"] = self._counts.get("preempted", 0) + 1
        extra = {"trace_id": req.tctx.trace_id} \
            if req.tctx is not None and req.tctx.sampled else {}
        _events.emit("decode", action="preempt", rid=req.rid,
                     generated=len(req.generated), tenant=req.tenant,
                     **extra)
        if _tracing.recording or req.traced:
            _tracing.record(
                "decode.preempt", req.enqueued_at, req.enqueued_at,
                "decode", parent=req.parent, rid=req.rid, ctx=req.tctx,
                generated=len(req.generated))
        self._kv_gauges()

    def _snapshot(self, C: int) -> Tuple[Tuple[int, ...],
                                         List[Optional[_Request]]]:
        slots: List[Optional[_Request]] = list(self._active[:C])
        while len(slots) < C:
            slots.append(None)
        return tuple(r.rid if r else -1 for r in slots), slots

    def _next_ids(self, sig, slots):
        """Where the ids of the decode step about to be dispatched with
        the batch `slots` come from: (how, the ids or what they are
        assembled from, the `_assemble` calls still to make on them, each
        a (program key, first token, index)). The host knows where each
        slot's last token lies without having it: in the row of the
        decode step in flight that carried the slot, in an admission's
        prefill still in flight, or (nothing in flight) in
        `req.last_token`. Nothing here enqueues: `_dispatch` makes the
        calls, back to back with the step's.

        "fed": the batch is the in-flight step's, whose tokens go back
        in as they are. "assembled": the batch changed (an admission, a
        retirement): `_assemble` gathers the rows on the device, one
        call, and one more for each further admission of the turn.
        "host": nothing is in flight (an empty engine's first step is
        not: its prefill is), the ids are built here."""
        prev = next((p for p in reversed(self._inflight)
                     if p.snapshot is not None), None)
        if prev is not None and prev.snapshot == sig:
            return "fed", prev.tok_dev, ()
        C = len(slots)
        if prev is None:
            # a drain left every resident's token on the host
            src = np.zeros((C,), np.int32)
            for i, req in enumerate(slots):
                if req is not None:
                    src[i] = req.last_token
            if not self._inflight:
                return "host", src, ()
            row = {req.rid: i for i, req in enumerate(slots)
                   if req is not None}
        else:
            src = prev.tok_dev
            row = {req.rid: i for i, req in enumerate(prev.slots)
                   if req is not None}
        firsts = {p.slots[0].rid: p for p in self._inflight
                  if p.snapshot is None}
        # a resident without a prefill in flight rode the step in flight:
        # it was admitted before that step, or everything was drained
        idx = np.zeros((C,), np.int32)
        late = []       # (slot, the prefill that holds its first token)
        for i, req in enumerate(slots):
            if req is None:
                continue
            if req.rid in firsts:
                late.append((i, firsts[req.rid]))
            else:
                idx[i] = row[req.rid]
        n = len(src)
        first = self._no_first
        if late:
            idx[late[0][0]] = n
            first = late[0][1].tok_dev
        calls = [((n, C), first, idx)]
        for i, p in late[1:]:
            idx = np.arange(C, dtype=np.int32)
            idx[i] = C
            calls.append(((C, C), p.tok_dev, idx))
        return "assembled", src, calls

    def _dispatch(self, C: int) -> _Pending:
        """Dispatch one decode step at `C` slots behind whatever is in
        flight, and queue its fetch."""
        sp = part = probe = ran = starved = None
        if _tracing.recording:
            sp = _tracing.open_span("decode.dispatch", "decode")
            probe = self._queue_empty()
            part = _tracing.open_span("decode.dispatch.build", "decode")
        sig, slots = self._snapshot(C)
        kp, vp = self._pools
        positions = np.zeros((C,), np.int32)
        bts = np.zeros((C, self.kv_cfg.max_blocks_per_seq), np.int32)
        rows = np.full((C,), NULL_ROW, np.int32)    # idle slots: the null row
        for i, req in enumerate(slots):
            if req is None:
                continue
            positions[i] = req.pos
            rows[i] = req.state_row
            bts[i] = build_block_table(req.blocks,
                                       self.kv_cfg.max_blocks_per_seq)
        state = (self._state, rows) if self._state_specs else ()
        if self._walloc is not None:
            wbts = np.zeros_like(bts)
            for i, req in enumerate(slots):
                if req is not None:
                    wbts[i] = window_table(req.wblocks, self._ring,
                                           self.kv_cfg.max_blocks_per_seq)
            state += (wbts,)
        how, ids_arg, assemblies = self._next_ids(sig, slots)
        self._pipeline[how] += 1
        if part is not None:
            # everything from here on enqueues
            part.close()
            if probe is None:
                # asked again: the step in flight at the opening may have
                # ended while the batch was built
                probe = self._queue_empty()
            part = _tracing.open_span("decode.dispatch.call", "decode")
        # last, so that an assembly and its step are dispatched back to
        # back: the device may be waiting for just these two
        for key, first, idx in assemblies:
            ids_arg = self._assemble[key](ids_arg, first, idx)
            if probe is not None and ran is None:
                ran = _tracing.clock()
        tok, kp, vp, stats, *out = self._decode[C](
            self.params, ids_arg, positions, kp, vp, bts, *state)
        if part is not None:
            called = part.close()
            if probe is not None:
                # the device starts with the first call that returned
                starved = (called if ran is None else ran) - probe
        self._pools = (kp, vp)
        if out:
            self._state = out[0]
        for req in slots:
            if req is not None:
                req.pos += 1
        STEPS.inc(phase="decode")
        OCCUPANCY.observe(sum(1 for r in slots if r is not None) / C)
        self._last_slot_config = C
        pending = _Pending(tok, sig, slots)
        self._inflight.append(pending)
        self._step_starts.append(pending.t_dispatch)
        if sp is not None:
            row = self._close_dispatch(
                sp, "decode", C, slots, ids=how,
                **({"queue_empty": False} if starved is None else
                   {"queue_empty": True, "starved_s": starved}))
            if stats is not None:
                # on their way to the host while the step runs on
                for a in jax.tree_util.tree_leaves(stats):
                    a.copy_to_host_async()
                pending.stats = (row, stats)
        return pending

    def _resolve(self, upto: Optional[_Pending] = None) -> None:
        """Consume what is in flight, oldest first (the device's order),
        up to the entry `upto` (all of it without one): a resident's
        token of step N-1 reaches its stream before the host waits for
        the prefill that was queued behind that step."""
        while self._inflight and self._inflight[0] is not upto:
            self._resolve_one(self._inflight.popleft())

    def _drain(self) -> None:
        """A forced drain: the host waits for everything in flight, so
        the device's queue runs empty before the next dispatch. Pool
        exhaustion, a request whose first token is its last, and the
        loop's end come here; `status()["pipeline"]["drains"]` counts."""
        if self._inflight:
            self._pipeline["drains"] += 1
            self._resolve()

    def _resolve_one(self, pending: _Pending) -> None:
        """Consume one fetch: stream a decode step's tokens or a
        prefill's first token, detect finishes, retire (freeing blocks).
        Tokens for slots that were retired, cancelled or preempted after
        the dispatch are discarded."""
        first = pending.snapshot is None
        sp = wait = None
        if _tracing.recording:
            sp = _tracing.open_span("decode.resolve", "decode")
            wait = _tracing.open_span("decode.resolve.wait", "decode")
        t_wait = time.perf_counter()
        toks = np.asarray(pending.handle.result()[0])
        now = time.perf_counter()
        if wait is not None:
            wait.close()
        if pending.stats is not None:
            self._note_step_stats(*pending.stats)
        wall = now - pending.t_dispatch
        if first:
            # live-MFU sample: the bucket executable's retained
            # cost_analysis FLOPs over the dispatch→resolve window (one
            # token emitted — the TTFT token)
            bucket = self._bucket_for_len(len(pending.slots[0].prompt))
            _perfwatch.record_step(
                "prefill", wall,
                flops=(self._prefill[bucket].current_cost() or {})
                .get("flops"),
                tokens=1, device_kind=self._device_kind)
        else:
            STEP_SECONDS.observe(wall)
            # live-MFU sample: the slot-config executable's retained
            # FLOPs over the dispatch→resolve window; the result() wait
            # is the host-blocked share, occupied slots are the tokens
            # produced
            C = len(pending.slots)
            _perfwatch.record_step(
                "decode", wall,
                flops=(self._decode[C].current_cost() or {}).get("flops"),
                tokens=sum(1 for r in pending.slots if r is not None),
                host_blocked=min(now - t_wait, wall),
                device_kind=self._device_kind)
        emitted = finished = 0
        for i, req in enumerate(pending.slots):
            if req is None or req not in self._active:
                continue
            self._emit_token(req, int(toks[i]),
                             phase="prefill" if first else "decode")
            emitted += 1
            reason = self._finished_reason(req)
            if reason:
                self._finish(req, reason)
                finished += 1
        if sp is not None:
            # the fetch's device array goes HERE, under a name, and not
            # where `_resolve` drops `pending` after the span: letting go
            # of it hands the interpreter lock over, 2 ms a turn beside
            # 128 readers (chip runs of PR 55 and PR 56)
            rel = _tracing.open_span("decode.resolve.release", "decode")
            pending.handle = pending.tok_dev = pending.stats = None
            rel.close()
            # a request's first token is its prefill's, not a step's
            sp.close(tokens=0 if first else emitted,
                     first=emitted if first else 0, finished=finished)

    def _turn(self) -> None:
        """One turn of the lazy loop: admit (prefills dispatched, their
        first tokens left on the device), grow, dispatch step N with ids
        that never came to the host (`_next_ids`), then resolve what is
        older than step N: step N-1, and the prefills queued behind it.
        Nothing in a turn waits for the device before the dispatch except
        a forced drain (`_drain`), so the device's queue stays non-empty
        across admissions and retirements; a sequence that ends rides one
        more step, whose token for it is discarded."""
        self._sweep_cancelled()
        self._admit()
        if not self._active:
            self._resolve()     # nothing to dispatch behind it
            self._flush_outbox()
            return
        self._grow_blocks()
        if not self._active:    # growth drained, then preempted everything
            self._flush_outbox()
            return
        newest = self._dispatch(self._slot_config())
        # the last turn's tokens to their readers, who write them while
        # the loop waits below (`_to_reader`)
        self._flush_outbox()
        # overlap: resolve step N-1 (and the admissions' first tokens)
        # while step N runs
        self._resolve(upto=newest)

    def _loop(self):
        self._outbox = []
        try:
            while True:
                with self._cv:
                    while not self._closed and not self._waiting \
                            and not self._active and not self._inflight:
                        self._cv.wait(timeout=0.5)
                    if self._closed:
                        break
                # the idle wait above lies outside the turn's span; the
                # CPU time of this thread is read at a turn's edges and
                # nowhere else (the clock is a system call)
                sp = None
                if _tracing.recording:
                    sp = _tracing.open_span("decode.turn", "decode")
                    cpu0 = time.thread_time()
                try:
                    self._turn()
                finally:
                    if sp is not None:
                        sp.close(loop="lazy",
                                 cpu_s=time.thread_time() - cpu0)
        except BaseException as e:  # scheduler death must not hang clients
            with self._cv:
                reqs = list(self._active) + list(self._waiting)
                self._waiting.clear()
            for req in reqs:
                req.error = RuntimeError(
                    f"decode scheduler failed: {type(e).__name__}: {e}")
                req.error.__cause__ = e
                self._finish(req, "error")
            raise
        finally:
            try:
                self._drain()
            except Exception:  # lint-exempt:swallow: shutdown path; clients are cancelled below
                pass
            with self._cv:
                reqs = list(self._active) + list(self._waiting)
                self._waiting.clear()
                QUEUE_DEPTH.set(0)
            for req in reqs:
                self._finish(req, "cancelled")
            box, self._outbox = self._outbox, None
            for req, item in box or ():
                self._hand_over(req, item)

    # -- KV-reuse scheduler (chunked prefill / prefix cache / spec) ----
    #
    # Any reuse feature runs THIS loop instead of _loop: synchronous
    # rounds (each resolves on the host before the next dispatch),
    # trading the lazy-fetch step overlap for mid-prompt admission —
    # one prompt chunk interleaves with every decode round — and for
    # multi-token speculation rounds.

    def _prefix_block_bytes(self) -> int:
        """Device bytes ONE cached block retains across both models'
        pools (both entries, all layers) — the unit of the memwatch
        prefix_cache owner row."""
        def per(kv: KVCacheConfig) -> int:
            return kv.layers * kv.block_size * kv.bytes_per_token()
        n = per(self.kv_cfg)
        if self._draft_kv_cfg is not None:
            n += per(self._draft_kv_cfg)
        return n

    def _reserve_chunked(self, req: _Request) -> bool:
        """Reserve the full block span for a prompt before chunking
        starts: prefix-cache hits splice cached blocks into the front
        of the table (skipping their recompute entirely), fresh blocks
        cover the rest. All-or-nothing — on a pool shortfall the hits
        are released (decref) and the request stays queued. Caller
        holds self._cv."""
        plen = len(req.prompt)
        bs = self.kv_cfg.block_size
        need = -(-plen // bs)
        reused: List[int] = []
        req.hashes = None
        if self.config.prefix_cache:
            req.hashes = _kvr.hash_blocks(req.prompt, bs)
            # block j is shareable iff (j+1)*bs <= plen-1: the computed
            # suffix must keep >= 1 prompt token, so the chunk program
            # always produces the first-token logits
            usable = [h for j, h in enumerate(req.hashes)
                      if (j + 1) * bs <= plen - 1]
            reused = self._alloc.match_prefix(usable)
        if not self._alloc.can_alloc(need - len(reused)):
            if reused:
                self._alloc.free(reused)
            return False
        req.blocks = list(reused) + self._alloc.alloc(need - len(reused))
        req.n_reused = len(reused)
        req.prefill_pos = len(reused) * bs
        return True

    def _admit_sync(self):
        """Admission for the sync loop: chunked prompts reserve their
        block span and join the prefilling stage (their compute is
        spread over later iterations); without chunking (spec-only
        engines) the whole-prompt prefill runs here as in _admit."""
        max_slots = self.decode_slots[-1]
        waiting = len(self._waiting)
        sp = _tracing.open_span("decode.admit", "decode") \
            if _tracing.recording and waiting else None
        admitted = 0
        while True:
            chunked = False
            with self._cv:
                if not self._waiting or self._closed:
                    break
                if len(self._active) + len(self._prefilling) \
                        >= max_slots:
                    break
                idx = self._pick_waiting_locked()
                req = self._waiting[idx]
                if self.prefill_chunk:
                    if not self._reserve_chunked(req):
                        break
                    chunked = True
                else:
                    need = -(-len(req.prompt) // self.kv_cfg.block_size)
                    if not self._alloc.can_alloc(need):
                        break
                del self._waiting[idx]
                QUEUE_DEPTH.set(len(self._waiting))
            admitted += 1
            if chunked:
                req.admitted_at = time.monotonic()
                if _tracing.recording or req.traced:
                    _tracing.record(
                        "decode.queue_wait", req.enqueued_at,
                        req.admitted_at, "decode", parent=req.parent,
                        rid=req.rid, ctx=req.tctx, tenant=req.tenant)
                if self._wfq is not None:
                    self._wfq.charge(req.tenant, len(req.prompt))
                self._prefilling.append(req)
                self._kv_gauges()
            else:
                first = self._prefill_one(req)
                if first is not None:
                    self._resolve_one(first)    # every round resolves
        if sp is not None:
            sp.close(waiting=waiting, admitted=admitted)

    def _pump_chunk(self):
        """Advance the FRONT prefilling request by one chunk (both
        models when a draft rides along). On the final chunk the
        request's full prompt blocks register in the prefix index, the
        first token emits, and the request joins the decode batch."""
        if not self._prefilling:
            return
        req = self._prefilling[0]
        sp = None
        if _tracing.recording or req.traced:
            sp = _tracing.open_span("decode.prefill", "decode",
                                    parent=req.parent, rid=req.rid,
                                    ctx=req.tctx)
        try:
            self._pump_front(req)
        finally:
            if sp is not None:
                sp.close(chunk=self.prefill_chunk,
                         prompt_len=len(req.prompt),
                         prefill_pos=req.prefill_pos,
                         reused_blocks=req.n_reused,
                         queue_wait_s=req.admitted_at - req.enqueued_at)

    def _pump_front(self, req: _Request):
        Ck = self.prefill_chunk
        bs = self.kv_cfg.block_size
        plen = len(req.prompt)
        start = req.prefill_pos
        cid = np.empty((1, Ck), np.int32)
        seg = req.prompt[start:start + Ck]
        cid[0, :len(seg)] = seg
        cid[0, len(seg):] = req.prompt[-1]     # edge-pad (in-distribution)
        bt = build_block_table(req.blocks, self.kv_cfg.max_blocks_per_seq)
        kp, vp = self._pools
        t0 = time.perf_counter()
        wait = None
        if _tracing.recording:
            self._step_record("chunk", t0, 1, 1, start + len(seg))
            wait = _tracing.open_span("decode.prefill.wait", "decode")
        tok, kp, vp = self._chunk[Ck](
            self.params, cid, np.int32(start), np.int32(plen), kp, vp,
            bt)
        self._pools = (kp, vp)
        STEPS.inc(phase="prefill")
        if self._draft is not None:
            dkp, dvp = self._draft_pools
            _, dkp, dvp = self._draft_chunk[Ck](
                self._draft_params, cid, np.int32(start), np.int32(plen),
                dkp, dvp, bt)
            self._draft_pools = (dkp, dvp)
            STEPS.inc(phase="draft")
        req.prefill_pos = start + Ck
        done = req.prefill_pos >= plen
        t_called = time.perf_counter()
        _perfwatch.record_step(
            "prefill", t_called - t0,
            flops=(self._chunk[Ck].current_cost() or {}).get("flops"),
            tokens=1 if done else 0, device_kind=self._device_kind)
        # only the slice that ends the prompt fetches a token: the wait
        # of an earlier one ends with the call
        tok0 = int(np.asarray(tok)[0]) if done else None
        if wait is not None:
            wait.close(call_s=t_called - t0)
        if not done:
            return
        if self.config.prefix_cache and req.hashes:
            # contents are final: full prompt blocks are never written
            # again (decode/verify writes land at positions >= plen)
            for j, h in enumerate(req.hashes):
                if (j + 1) * bs <= plen - 1:
                    self._alloc.register(req.blocks[j], h)
        req.pos = plen
        req.draft_pos = plen
        self._prefilling.popleft()
        self._active.append(req)
        self._emit_token(req, tok0, phase="prefill")
        reason = self._finished_reason(req)
        if reason:
            self._finish(req, reason)
        self._kv_gauges()

    def _cow_guard(self, req: _Request, lo: int, hi: int):
        """Copy-on-write safety net: any SHARED block among req's
        block indices [lo, hi] (the imminent write span) is replaced
        by a private device copy before the write. Unreachable in the
        normal flow — shared blocks live strictly inside the prompt
        prefix and writes land at positions >= prompt length — but a
        forced share (tests; future partial-block reuse) must not let
        one sequence corrupt another's prefix."""
        if not self.config.prefix_cache:
            return
        for bi in range(lo, min(hi, len(req.blocks) - 1) + 1):
            blk = req.blocks[bi]
            if not self._alloc.is_shared(blk):
                continue
            new = self._alloc.cow_alloc(blk)
            kp, vp = self._pools
            kp, vp = self._copy_block_fn(kp, vp, blk, new)
            self._pools = (kp, vp)
            if self._draft_pools is not None:
                dkp, dvp = self._draft_pools
                dkp, dvp = self._copy_block_fn(dkp, dvp, blk, new)
                self._draft_pools = (dkp, dvp)
            req.blocks[bi] = new

    def _grow_blocks_sync(self, span: int):
        """Every active slot owns (privately) the blocks its next
        `span` KV writes land in. On pool exhaustion the youngest
        admitted sequence — active or still prefilling — is preempted
        until the round fits."""
        bs = self.kv_cfg.block_size
        sp = _tracing.open_span("decode.grow", "decode") \
            if _tracing.recording else None
        taken = preempted = 0
        while True:
            short = None
            try:
                for req in self._active:
                    lo = req.pos // bs
                    hi = (req.pos + span - 1) // bs
                    while hi >= len(req.blocks):
                        self._alloc.grow(req.blocks)
                        taken += 1
                    self._cow_guard(req, lo, hi)
            except NoBlocksError:
                short = req
            if short is None:
                break
            candidates = list(self._active) + list(self._prefilling)
            victim = max(candidates, key=self._victim_key)
            self._preempt(victim)
            preempted += 1
            if not self._active:
                break
        if sp is not None:
            sp.close(blocks=taken, preempted=preempted)

    def _step_plain_sync(self):
        """One synchronous decode round: every active slot advances
        one token. With a draft model present (speculation's near-
        max_len fallback) the draft runs the same round in lockstep so
        its KV stays position-aligned for the next spec round."""
        self._grow_blocks_sync(1)
        if not self._active:
            return
        sp = part = None
        if _tracing.recording:
            sp = _tracing.open_span("decode.dispatch", "decode")
            part = _tracing.open_span("decode.dispatch.build", "decode")
        C = self._slot_config()
        sig, slots = self._snapshot(C)
        ids = np.zeros((C,), np.int32)
        positions = np.zeros((C,), np.int32)
        bts = np.zeros((C, self.kv_cfg.max_blocks_per_seq), np.int32)
        for i, req in enumerate(slots):
            if req is None:
                continue
            ids[i] = req.last_token
            positions[i] = req.pos
            bts[i] = build_block_table(req.blocks,
                                       self.kv_cfg.max_blocks_per_seq)
        if part is not None:
            part.close()
            part = _tracing.open_span("decode.dispatch.call", "decode")
        t0 = time.perf_counter()
        self._step_starts.append(t0)
        kp, vp = self._pools
        tok, kp, vp, stats = self._decode[C](self.params, ids, positions,
                                             kp, vp, bts)
        self._pools = (kp, vp)
        if self._draft is not None:
            self._draft_catch_up()
            dkp, dvp = self._draft_pools
            _, dkp, dvp = self._draft_decode[C](
                self._draft_params, ids, positions, dkp, dvp, bts)
            self._draft_pools = (dkp, dvp)
            STEPS.inc(phase="draft")
        if part is not None:
            part.close()
        res, wait, row = self._sync_resolve_spans(sp, "decode", C, slots)
        toks = np.asarray(tok)                 # synchronous resolve
        if wait is not None:
            wait.close()
        if row is not None and stats is not None:
            self._note_step_stats(row, stats)
        wall = time.perf_counter() - t0
        STEP_SECONDS.observe(wall)
        STEPS.inc(phase="decode")
        occupied = sum(1 for r in slots if r is not None)
        OCCUPANCY.observe(occupied / C)
        self._last_slot_config = C
        _perfwatch.record_step(
            "decode", wall,
            flops=(self._decode[C].current_cost() or {}).get("flops"),
            tokens=occupied, device_kind=self._device_kind)
        emitted = finished = 0
        for i, req in enumerate(slots):
            if req is None or req not in self._active:
                continue
            req.pos += 1
            if self._draft is not None:
                req.draft_pos = req.pos
            self._emit_token(req, int(toks[i]), phase="decode")
            emitted += 1
            reason = self._finished_reason(req)
            if reason:
                self._finish(req, reason)
                finished += 1
        if res is not None:
            res.close(tokens=emitted, finished=finished)

    def _close_dispatch(self, sp, kind: str, C: int, slots,
                        ids: str = "host", **probed) -> Dict:
        """Recording on: the step's record (returned) and the end of its
        open decode.dispatch span `sp`; `ids` says where the step's ids
        came from (`_next_ids`; a synchronous round builds them on the
        host), `probed` what the lazy loop saw of the device's queue
        (`_queue_empty`: `queue_empty`, and `starved_s` where it was)."""
        live = [r for r in slots if r is not None]
        tokens = sum(r.pos for r in live)
        row = self._step_record(kind, sp.t0, C, len(live), tokens,
                                self._window_tokens(live))
        # the window kind's facts, where the model has one: the row's
        windows = {k: v for k, v in row.items() if k.startswith("window_")}
        sp.close(slots=C, live=len(live), live_tokens=tokens,
                 blocks_used=self._alloc.used_blocks(), ids=ids, **windows,
                 **probed)
        return row

    def _sync_resolve_spans(self, sp, kind: str, C: int, slots):
        """A synchronous round has dispatched: close its decode.dispatch
        span `sp` and open the (decode.resolve, decode.resolve.wait) pair
        that follows it, with the step's record; (None, None, None) with
        recording off."""
        if sp is None:
            return None, None, None
        row = self._close_dispatch(sp, kind, C, slots)
        return (_tracing.open_span("decode.resolve", "decode"),
                _tracing.open_span("decode.resolve.wait", "decode"), row)

    def _draft_catch_up(self):
        """After a fully-accepted spec round the draft's KV trails the
        target by EXACTLY one position (the round's bonus token never
        passed through the draft). One batched draft step feeds each
        lagging slot the token AT its missing position; non-lagging
        slots ride along with all-zero block tables, so their writes
        land in the null block."""
        if not any(r.draft_pos < r.pos for r in self._active):
            return
        C = self._slot_config()
        sig, slots = self._snapshot(C)
        ids = np.zeros((C,), np.int32)
        positions = np.zeros((C,), np.int32)
        bts = np.zeros((C, self.kv_cfg.max_blocks_per_seq), np.int32)
        for i, req in enumerate(slots):
            if req is None or req.draft_pos >= req.pos:
                continue
            # token at position pos-1 is the second-newest emission
            ids[i] = req.generated[-2] if len(req.generated) >= 2 \
                else int(req.prompt[-1])
            positions[i] = req.draft_pos
            bts[i] = build_block_table(req.blocks,
                                       self.kv_cfg.max_blocks_per_seq)
        dkp, dvp = self._draft_pools
        _, dkp, dvp = self._draft_decode[C](
            self._draft_params, ids, positions, dkp, dvp, bts)
        self._draft_pools = (dkp, dvp)
        STEPS.inc(phase="draft")
        for req in slots:
            if req is not None and req.draft_pos < req.pos:
                req.draft_pos += 1

    def _step_spec(self):
        """One speculation round: k device-chained draft proposals,
        one batched target verification, exact greedy accept — the
        emitted stream is bit-identical to plain decode, at up to k+1
        tokens per target step. A slot too close to max_len for the
        k+1-token span demotes the WHOLE round to the plain path (the
        batch always runs one program per round)."""
        k = self.spec_k
        if any(r.pos + k > self.kv_cfg.max_len - 1
               for r in self._active):
            self._step_plain_sync()
            return
        self._grow_blocks_sync(k + 1)
        if not self._active:
            return
        sp = part = None
        if _tracing.recording:
            sp = _tracing.open_span("decode.dispatch", "decode")
            part = _tracing.open_span("decode.dispatch.build", "decode")
        self._draft_catch_up()
        C = self._slot_config()
        sig, slots = self._snapshot(C)
        ids = np.zeros((C,), np.int32)
        positions = np.zeros((C,), np.int32)
        bts = np.zeros((C, self.kv_cfg.max_blocks_per_seq), np.int32)
        for i, req in enumerate(slots):
            if req is None:
                continue
            ids[i] = req.last_token
            positions[i] = req.pos
            bts[i] = build_block_table(req.blocks,
                                       self.kv_cfg.max_blocks_per_seq)
        if part is not None:
            # the draft chain, its sync point and the verification
            part.close()
            part = _tracing.open_span("decode.dispatch.call", "decode")
        t0 = time.perf_counter()
        self._step_starts.append(t0)
        # k draft steps, each feeding the previous step's DEVICE token
        # — the chain dispatches without a host sync
        dkp, dvp = self._draft_pools
        dtok = ids
        drafts = []
        for j in range(k):
            dtok, dkp, dvp = self._draft_decode[C](
                self._draft_params, dtok,
                (positions + j).astype(np.int32), dkp, dvp, bts)
            drafts.append(dtok)
            STEPS.inc(phase="draft")
        self._draft_pools = (dkp, dvp)
        ids_v = np.empty((C, k + 1), np.int32)
        ids_v[:, 0] = ids
        for j, d in enumerate(drafts):         # draft-chain sync point
            ids_v[:, j + 1] = np.asarray(d)
        kp, vp = self._pools
        vtok, kp, vp = self._verify[C](self.params, ids_v, positions,
                                       kp, vp, bts)
        self._pools = (kp, vp)
        STEPS.inc(phase="verify")
        if part is not None:
            part.close()
        res, wait, _ = self._sync_resolve_spans(sp, "verify", C, slots)
        outs = np.asarray(vtok)                # [C, k+1]
        if wait is not None:
            wait.close()
        wall = time.perf_counter() - t0
        STEP_SECONDS.observe(wall)
        occupied = sum(1 for r in slots if r is not None)
        OCCUPANCY.observe(occupied / C)
        self._last_slot_config = C
        emitted = finished = 0
        for i, req in enumerate(slots):
            if req is None or req not in self._active:
                continue
            props = [int(x) for x in ids_v[i, 1:]]
            row = [int(x) for x in outs[i]]
            a = _kvr.accept_length(props, row)
            self._spec_proposed += k
            self._spec_accepted += a
            pos0 = req.pos
            remaining = req.max_new - len(req.generated)
            emit = []
            for t in row[:min(a + 1, remaining)]:
                emit.append(t)
                if t == self.eos_id:
                    break
            req.pos = pos0 + len(emit)
            # full accept leaves the draft one position behind (the
            # bonus token o_k never passed through it); any rejection
            # lands draft_pos exactly at the new pos
            req.draft_pos = min(pos0 + k, req.pos)
            for t in emit:
                self._emit_token(req, int(t), phase="decode")
            emitted += len(emit)
            reason = self._finished_reason(req)
            if reason:
                self._finish(req, reason)
                finished += 1
        if res is not None:
            res.close(tokens=emitted, finished=finished)
        if self._spec_proposed:
            _kvr.SPEC_ACCEPT_RATE.set(
                self._spec_accepted / self._spec_proposed)
        _perfwatch.record_step(
            "decode", wall,
            flops=(self._verify[C].current_cost() or {}).get("flops"),
            tokens=emitted, device_kind=self._device_kind)

    def _turn_sync(self):
        self._sweep_cancelled()
        self._admit_sync()
        self._pump_chunk()             # one slice per iteration
        if not self._active:
            return
        if self.spec_k:
            self._step_spec()
        else:
            self._step_plain_sync()

    def _loop_sync(self):
        try:
            while True:
                with self._cv:
                    while not self._closed and not self._waiting \
                            and not self._active \
                            and not self._prefilling:
                        self._cv.wait(timeout=0.5)
                    if self._closed:
                        break
                sp = None
                if _tracing.recording:
                    sp = _tracing.open_span("decode.turn", "decode")
                    cpu0 = time.thread_time()
                try:
                    self._turn_sync()
                finally:
                    if sp is not None:
                        sp.close(loop="sync",
                                 cpu_s=time.thread_time() - cpu0)
        except BaseException as e:  # scheduler death must not hang clients
            with self._cv:
                reqs = (list(self._active) + list(self._prefilling) +
                        list(self._waiting))
                self._waiting.clear()
            for req in reqs:
                req.error = RuntimeError(
                    f"decode scheduler failed: {type(e).__name__}: {e}")
                req.error.__cause__ = e
                self._finish(req, "error")
            raise
        finally:
            with self._cv:
                reqs = (list(self._active) + list(self._prefilling) +
                        list(self._waiting))
                self._waiting.clear()
                QUEUE_DEPTH.set(0)
            for req in reqs:
                self._finish(req, "cancelled")
