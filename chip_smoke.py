#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, the entry points a user would call, published widths, random
weights from a fixed seed. Each phase prints one JSON info line (compile
seconds, run seconds, what it checked); the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any failed phase, or a platform other than "tpu", ends the run with
`"ok": false` in that shape and a non-zero exit code: there is no CPU or
interpreter fallback anywhere below, and no phase's exception is passed
over. Numbers printed here are information, not benchmark results.

    python chip_smoke.py            one chip: device, program, train,
                                    long_seq, serve, serve_reuse,
                                    serve_olmoe, serve_joyai, serve_xing4,
                                    serve_nemotron, serve_jamba,
                                    serve_longcat, longcat_experts,
                                    serve_granite, granite_experts,
                                    serve_trinity, trinity_experts,
                                    paged_attention
    python chip_smoke.py --chips 4  the cross-chip path only: BERT-base
                                    sharded dp x tp=2 vs the same batch on
                                    one device, then the dp/tp/sp/pp/ep
                                    compositions with kernels compiled

JAX's persistent compilation cache sits where JAX_COMPILATION_CACHE_DIR
says, else at <checkout>/.jax_cache (core/compile_cache.place_jax_cache),
so a second run in the same checkout skips XLA; each phase line carries
the cache's hits and misses. The script starts no child process and takes
no lock: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import json
import sys
import threading
import time

SEED = 0
OLMOE_LOGIT_TOL = 0.25   # benchmarks/configs/olmoe_1b_7b.json argues it
JOYAI_LOGIT_TOL = 0.4    # benchmarks/configs/joyai_llm_flash.json argues it
XING4_LOGIT_TOL = 0.55   # benchmarks/configs/xing4_29b_a4b.json argues it
NEMOTRON_LOGIT_TOL = 0.4  # benchmarks/configs/nemotron3_nano.json argues it
JAMBA_LOGIT_TOL = 2.5    # benchmarks/configs/jamba2_3b.json argues it
LONGCAT_LOGIT_TOL = 0.3  # benchmarks/configs/longcat_flash_chat.json argues it
GRANITE_LOGIT_TOL = 0.03  # benchmarks/configs/granite4_h_small.json argues it
TRINITY_LOGIT_TOL = 0.5   # benchmarks/configs/trinity_mini.json argues it

# jax.monitoring feed: how many programs JAX was asked to compile, and how
# many of those its persistent cache answered (a hit still counts as a
# request — "zero compiles after warm-up" means zero NEW programs)
_COUNTS: collections.Counter = collections.Counter()
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
_COMPILE_REQUEST = "/jax/core/compile/backend_compile_duration"


def _listen():
    import jax

    def on_event(event, **kw):
        if event in _EVENTS:
            _COUNTS[_EVENTS[event]] += 1

    def on_duration(event, secs, **kw):
        if event == _COMPILE_REQUEST:
            _COUNTS["compile_requests"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


@contextlib.contextmanager
def phase(name: str):
    """Yield the phase's info dict; print it as the phase's JSON line once
    the body has filled it in — only when the body did not raise."""
    import jax

    info: dict = {}
    before = collections.Counter(_COUNTS)
    t0 = time.perf_counter()
    yield info
    print(json.dumps(dict(
        phase=name, **info, wall_s=round(time.perf_counter() - t0, 2),
        **{k: _COUNTS[k] - before[k] for k in
           ("compile_requests", "cache_hits", "cache_misses")})), flush=True)
    # the next phase gets the whole chip: drop executables and dead arrays
    jax.clear_caches()
    gc.collect()


def _timed(fn):
    """(result, seconds) with the device drained inside the timed region."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _init(model, cfg):
    """(params, axes) of `model.init(key, cfg)` as ONE compiled program:
    called eagerly, init costs a compile per distinct parameter shape."""
    import jax

    axes: dict = {}

    def init(key):
        params, a = model.init(key, cfg)
        axes.update(a)  # static: filled once, while tracing
        return params

    return jax.jit(init)(jax.random.key(SEED)), axes


# ---------------------------------------------------------------------------
# phases — each returns facts; what must hold on the chip is asserted by
# the caller, what must hold anywhere is asserted here
# ---------------------------------------------------------------------------


def device_phase(info: dict) -> dict:
    """jax.devices() as the driver reads it; the attached device_kind
    must be a row of the peaks table by itself, not via DEFAULT_PEAK."""
    import jax

    from paddle_tpu.observability import device_peaks

    devs = jax.devices()
    info.update(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    info["peaks_row"] = next(
        (key for key, _ in device_peaks.PEAKS
         if key in devs[0].device_kind.lower()), None)
    return info


def program_phase(info: dict, place, steps: int = 40) -> dict:
    """The README quickstart surface: a LeNet Program through
    fluid.Executor(place), numpy in, a falling loss out."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import lenet

    # batch 64: at 256 the TPU compiler alone takes ~90 s over this
    # step (conv1's weight gradient, Cin=1; PERF.md) — the phase is about
    # the Program/Executor surface, not about a batch size
    rng = np.random.RandomState(SEED)
    feed = {"img": rng.rand(64, 1, 28, 28).astype("float32"),
            "label": rng.randint(0, 10, (64, 1)).astype("int64")}
    main, startup, _, loss, _ = lenet.build_program(pt, lr=2e-3)
    exe = pt.Executor(place)

    def run_once():
        return float(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss])[0]).reshape(()))

    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        t0 = time.perf_counter()
        losses = [run_once()]
        info["compile_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        losses += [run_once() for _ in range(steps)]
        info["run_s"] = round(time.perf_counter() - t0, 2)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"LeNet loss did not fall: {losses}"
    info["checked"] = {"steps": steps, "loss_first": round(losses[0], 4),
                       "loss_last": round(losses[-1], 4)}
    return info


def _bert_driver(cfg, batch_size, seq_len, mesh, deterministic):
    """(state, step, batch) built as the benchmark's BERT cells build
    them: make_train_step + ZeRO-1 optimizer state under the mesh."""
    import jax
    import optax

    from paddle_tpu.models import bert
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    params, axes = _init(bert, cfg)

    def loss_fn(p, b, r):
        return bert.pretrain_loss(p, cfg, b, rng=r,
                                  deterministic=deterministic)

    init_state, step = make_train_step(
        loss_fn, optax.adamw(1e-4), mesh, axes,
        strategy=TrainStrategy(shard_optimizer_states=True))
    state = init_state(params)
    batch = bert.make_batch(jax.random.key(SEED + 1), cfg,
                            batch_size=batch_size, seq_len=seq_len)
    return state, step, batch


def train_phase(info: dict, cfg, batch_size: int, seq_len: int, mesh,
                steps: int = 5, deterministic: bool = False,
                want_text: bool = False):
    """A few optimizer steps on one fixed batch: one warm step (compile),
    `steps` timed steps ended by block_until_ready, finite and falling
    loss. Returns (info, losses, state, compiled_text_or_None)."""
    import jax
    import numpy as np

    from paddle_tpu.ops.pallas import attention
    from paddle_tpu.parallel import mesh_guard

    attention.GATE_COUNTS.clear()
    with mesh_guard(mesh):
        state, step, batch = _bert_driver(cfg, batch_size, seq_len, mesh,
                                          deterministic)
        rng = jax.random.key(SEED + 2)
        (state, loss0), compile_s = _timed(lambda: step(state, batch, rng))
        losses = [loss0]

        def run():
            nonlocal state
            for _ in range(steps):
                state, loss = step(state, batch, rng)
                losses.append(loss)
            return state, loss

        _, run_s = _timed(run)
        # the very program the steps ran, read back from its lowering
        # (the persistent cache answers this compile)
        text = step.lower(state, batch, rng).compile().as_text() \
            if want_text else None
    losses = [float(x) for x in losses]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    info.update(compile_s=round(compile_s, 2), run_s=round(run_s, 2),
                step_ms=round(1000 * run_s / steps, 2),
                checked={"batch_size": batch_size, "seq_len": seq_len,
                         "steps": steps, "losses": [round(x, 4)
                                                    for x in losses],
                         "gate": dict(attention.GATE_COUNTS)})
    return info, losses, state, text


def _post_generate(port: int, ids, max_new: int, out: dict, key):
    """One streamed POST /v1/generate; the parsed ndjson records land in
    out[key] (or the exception does — the caller raises it)."""
    import http.client

    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            conn.request("POST", "/v1/generate",
                         body=json.dumps({"ids": [int(t) for t in ids],
                                          "max_new_tokens": max_new}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {resp.read()!r}")
            out[key] = [json.loads(ln) for ln in resp if ln.strip()]
        finally:
            conn.close()
    except Exception as e:  # re-raised on the main thread by the caller
        out[key] = e


def _reference_gaps(params, cfg, prompts, streams):
    """Teacher-forced check of every request against a plain float32
    full-forward `gpt.apply` of the same params, one unbatched row at a
    time: for each generated token, how far its reference logit lies
    below the reference argmax's (0 = the reference picks the same
    token). No KV cache, no engine code, no kernel (the flag is `off`
    while it runs). Rows are padded to one length — causal attention
    keeps the padding out of every position that is read."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.models import gpt

    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    n_new = len(streams[0])
    width = max(len(p) for p in prompts) + n_new

    @jax.jit
    def forward(params, ids, first):
        # rows first..first+n_new-1: row len(prompt)-1+i predicts token i
        return jax.lax.dynamic_slice_in_dim(
            gpt.apply(params, ref_cfg, ids)[0], first, n_new)

    gaps, exact = [], 0
    set_flags({"FLAGS_flash_attention": "off"})
    try:
        with jax.default_matmul_precision("highest"):
            for prompt, generated in zip(prompts, streams):
                ids = np.zeros((1, width), np.int32)
                ids[0, :len(prompt) + n_new] = prompt + generated
                rows = np.asarray(forward(params, jnp.asarray(ids),
                                          len(prompt) - 1), np.float32)
                picked = rows[np.arange(n_new), generated]
                gaps.append(float((rows.max(axis=-1) - picked).max()))
                exact += int((rows.argmax(axis=-1) == generated).sum())
    finally:
        set_flags({"FLAGS_flash_attention": "auto"})
    return max(gaps), exact


def _olmoe_reference_gaps(params, cfg, prompts, streams):
    """As `_reference_gaps`, against the benchmark's plain float32 OLMoE
    (benchmarks/reference/olmoe_ref.py: written from the published
    description, every expert computed for every token, no code of
    models/olmoe.py)."""
    from benchmarks.reference import olmoe_ref

    ref = {"layers": cfg.layers, "heads": cfg.heads, "top_k": cfg.top_k,
           "rope_theta": cfg.rope_theta, "rms_eps": cfg.rms_eps}
    top = {k: v for k, v in params.items() if not k.startswith("blk.")}
    width = max(len(p) for p in prompts) + len(streams[0])
    return olmoe_ref.stream_gaps(
        top, lambda i: olmoe_ref.layer_of(params, i), ref, prompts,
        streams, width)


def _joyai_reference_gaps(params, cfg, prompts, streams):
    """As `_olmoe_reference_gaps`, against the benchmark's plain float32
    latent-attention model in the EXPANDED form (benchmarks/reference/
    joyai_ref.py; no cache, no code of models/joyai.py)."""
    from benchmarks.reference import joyai_ref

    ref = {k: getattr(cfg, k) for k in (
        "layers", "dense_layers", "heads", "kv_rank", "nope_dim", "rope_dim",
        "v_dim", "top_k", "route_scale", "rope_theta", "rms_eps")}
    top = {k: v for k, v in params.items()
           if not k.startswith(("blk.", "dense."))}
    width = max(len(p) for p in prompts) + len(streams[0])
    return joyai_ref.stream_gaps(
        top, lambda i: joyai_ref.layer_of(params, ref, i), ref, prompts,
        streams, width)


def _xing4_reference_gaps(params, cfg, prompts, streams):
    """As `_joyai_reference_gaps`, against the benchmark's plain float32
    model of four residual streams (benchmarks/reference/xing4_ref.py: the
    maps and all 20 Sinkhorn rounds token by token, YaRN's frequencies, the
    expanded attention; no cache, no code of models/xing4.py)."""
    from benchmarks.reference import xing4_ref

    import dataclasses

    ref = dataclasses.asdict(cfg)       # the reference reads them by name
    top = {k: v for k, v in params.items()
           if not k.startswith(("blk.", "dense."))}
    return xing4_ref.stream_gaps(
        top, lambda i: xing4_ref.layer_of(params, ref, i), ref, prompts,
        streams, 0)


def _pattern_reference_gaps(ref_mod, params, cfg, prompts, streams):
    """A model of one mixer a block against its plain float32 reference
    `ref_mod` (the blocks of a kind stacked under the prefixes `ref_mod
    .PREFIX` names, handed over one block at a time)."""
    import dataclasses

    ref = dataclasses.asdict(cfg)
    top = {k: v for k, v in params.items()
           if not k.startswith(tuple(ref_mod.PREFIX.values()))}
    width = max(len(p) for p in prompts) + len(streams[0])
    return ref_mod.stream_gaps(
        top, lambda i: ref_mod.layer_of(params, ref, i), ref, prompts,
        streams, width)


def _nemotron_reference_gaps(params, cfg, prompts, streams):
    """As `_olmoe_reference_gaps`, against the benchmark's plain float32
    Nemotron-H (benchmarks/reference/nemotron_h_ref.py: the recurrence
    token by token, every expert for every token, no cache, no state pool,
    no code of models/nemotron_h.py)."""
    from benchmarks.reference import nemotron_h_ref

    return _pattern_reference_gaps(nemotron_h_ref, params, cfg, prompts,
                                   streams)


def _granite_reference_gaps(params, cfg, prompts, streams):
    """As `_nemotron_reference_gaps`, against the benchmark's plain float32
    Granite 4.0-H (benchmarks/reference/granite_hybrid_ref.py: a block at a
    time, the published router's order, no code of
    models/granite_hybrid.py)."""
    from benchmarks.reference import granite_hybrid_ref

    return _pattern_reference_gaps(granite_hybrid_ref, params, cfg, prompts,
                                   streams)


def _trinity_reference_gaps(params, cfg, prompts, streams):
    """As `_granite_reference_gaps`, against the benchmark's plain float32
    Trinity (benchmarks/reference/afmoe_ref.py: a block at a time, no
    cache, no ring, no slices, no code of models/afmoe.py)."""
    import dataclasses

    from benchmarks.reference import afmoe_ref

    ref = dict(dataclasses.asdict(cfg), q_block=min(256, cfg.prompt_slice))
    top = {k: v for k, v in params.items()
           if k.split(".")[0] in ("wte", "ln_f", "head")}
    width = max(len(p) for p in prompts) + len(streams[0])
    return afmoe_ref.stream_gaps(
        top, lambda b: afmoe_ref.layer_of(params, ref, b), ref, prompts,
        streams, width)


def _jamba_reference_gaps(params, cfg, prompts, streams):
    """As `_nemotron_reference_gaps`, against the benchmark's plain float32
    Jamba (benchmarks/reference/jamba_ref.py: the selective recurrence
    token by token, no cache, no state pool, no code of models/jamba.py)."""
    from benchmarks.reference import jamba_ref

    return _pattern_reference_gaps(jamba_ref, params, cfg, prompts, streams)


def _longcat_reference_gaps(params, cfg, prompts, streams):
    """As `_joyai_reference_gaps`, against the benchmark's plain float32
    LongCat (benchmarks/reference/longcat_ref.py: both sub-blocks and the
    shortcut a layer, every held expert for every token, the expanded
    attention; no cache, no code of models/longcat.py)."""
    from benchmarks.reference import longcat_ref

    import dataclasses

    ref = dataclasses.asdict(cfg)       # the reference reads them by name
    top = {k: v for k, v in params.items() if not k.startswith("blk.")}
    width = max(len(p) for p in prompts) + len(streams[0])
    return longcat_ref.stream_gaps(
        top, lambda i: longcat_ref.layer_of(params, ref, i), ref, prompts,
        streams, width)


def serve_phase(info: dict, cfg, decode_cfg, prompts, max_new: int,
                logit_tol: float, model=None,
                reference_gaps=_reference_gaps) -> dict:
    """DecodeEngine -> warmup -> Server.start -> concurrent streamed
    POST /v1/generate from threads of this process. Every stream must end
    `done` and is checked against the float32 reference; nothing may
    compile after warm-up. `model` is the module `cfg` belongs to
    (models.gpt unless given), `reference_gaps` its plain reference."""
    import jax
    import numpy as np

    from paddle_tpu.models import gpt
    from paddle_tpu.ops.pallas import (grouped_matmul, paged_attention,
                                       ssm_update)
    from paddle_tpu.serving import Server, ServingConfig, kv_cache
    from paddle_tpu.serving.decode import DecodeEngine

    params, _ = _init(model or gpt, cfg)
    grouped_matmul.GATE_COUNTS.clear()
    grouped_matmul.TILES.clear()
    paged_attention.GATE_COUNTS.clear()
    ssm_update.GATE_COUNTS.clear()
    kv_cache.PREFILL_WRITE_UNITS.clear()
    engine = DecodeEngine(params, cfg, decode_cfg)
    # prefill buckets, slot configurations, and the id assembly of each
    # pair of slot configurations
    n_phases = len(engine.prefill_buckets) + len(engine.decode_slots) \
        + len(engine.decode_slots) ** 2
    ready, compile_s = _timed(engine.warmup)
    assert ready == n_phases, f"only {ready}/{n_phases} phases compiled"
    server = Server(ServingConfig(), decode=engine)
    port = server.start(0)
    try:
        after_warmup = _COUNTS["compile_requests"]
        out: dict = {}
        threads = [threading.Thread(target=_post_generate,
                                    args=(port, p, max_new, out, i))
                   for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        run_s = time.perf_counter() - t0
        late_compiles = _COUNTS["compile_requests"] - after_warmup
        status = engine.status()
    finally:
        server.stop()
        engine.stop()
    streams = []
    for i in range(len(prompts)):
        recs = out.get(i)
        if isinstance(recs, Exception):
            raise recs
        assert recs, f"request {i} never answered"
        done = recs[-1]
        assert done.get("done") and "error" not in done, done
        toks = [r["token"] for r in recs if "token" in r]
        assert len(toks) == done["tokens"] == max_new, (i, done)
        assert all(0 <= t < cfg.vocab_size for t in toks)
        streams.append(toks)
    assert late_compiles == 0, \
        f"{late_compiles} programs compiled after warm-up"
    gap, exact = reference_gaps(params, cfg, prompts, streams)
    assert gap <= logit_tol, (
        f"engine tokens leave the float32 reference's argmax by up to "
        f"{gap:.4f} logits (tolerance {logit_tol})")
    info.update(
        compile_s=round(compile_s, 2), run_s=round(run_s, 2),
        checked={"requests": len(prompts),
                 "prompt_lens": [len(p) for p in prompts],
                 "max_new_tokens": max_new, "phases_warmed": ready,
                 "compiles_after_warmup": late_compiles,
                 "finished": status["requests"],
                 "decode_attention": status["decode_attention"],
                 "expert_matmul": status["expert_matmul"],
                 "prefill_write": status["prefill_write"],
                 # the state row pool of a model with recurrent layers
                 "state": status.get("state"),
                 "ref_exact_tokens": f"{exact}/{len(prompts) * max_new}",
                 "ref_max_logit_gap": round(gap, 5),
                 "ref_logit_tol": logit_tol})
    return info


def reuse_phase(info: dict, cfg, decode_cfg, prompt, max_new: int) -> dict:
    """A second engine with chunked prefill + the prefix cache on, so the
    synchronous scheduler (`_loop_sync`) runs too: the same prompt twice;
    the second resolves its prefix from the cache and must stream the
    same tokens as the first, which recomputed it."""
    import jax

    from paddle_tpu.models import gpt
    from paddle_tpu.ops.pallas import paged_attention
    from paddle_tpu.serving.decode import DecodeEngine

    params, _ = _init(gpt, cfg)
    paged_attention.GATE_COUNTS.clear()
    engine = DecodeEngine(params, cfg, decode_cfg)
    ready, compile_s = _timed(engine.warmup)
    assert ready == 1 + len(engine.decode_slots), ready
    try:
        after_warmup = _COUNTS["compile_requests"]
        t0 = time.perf_counter()
        first = engine.submit(prompt, max_new_tokens=max_new).result(
            timeout_s=600)
        second = engine.submit(prompt, max_new_tokens=max_new).result(
            timeout_s=600)
        run_s = time.perf_counter() - t0
        late_compiles = _COUNTS["compile_requests"] - after_warmup
        status = engine.status()
        kv = status["kv"]
    finally:
        engine.stop()
    assert len(first) == max_new and first == second, (first, second)
    assert kv["prefix_hits_total"] >= 1, kv
    assert late_compiles == 0, \
        f"{late_compiles} programs compiled after warm-up"
    info.update(compile_s=round(compile_s, 2), run_s=round(run_s, 2),
                checked={"prompt_len": len(prompt),
                         "prefill_chunk": engine.prefill_chunk,
                         "prefix_hits": kv["prefix_hits_total"],
                         "blocks_reused": kv["blocks_reused_total"],
                         "reused_equals_recomputed": True,
                         "decode_attention": status["decode_attention"],
                         "compiles_after_warmup": late_compiles})
    return info


def paged_attention_phase(info: dict, heads: int, head_dim: int,
                          layers: int, slots: int = 16,
                          table_blocks: int = 64, tol: float = 0.05,
                          interpret=False) -> dict:
    """The paged decode-attention kernel against the gather path
    (`decoder.cached_attention` of `mha_cached`) on one device: unit-normal
    noise in q and in every slot of both pools, scattered tables, lengths from one token
    to a full table with block and chunk edges and inactive slots among
    them, every layer of the pool. `tol` bounds the largest absolute
    difference of a live slot's context: both routes weigh in bf16 and
    round the result to bf16 (2^-8 of values up to about 4), the gather
    path also rounds its scores. `interpret` is for the rehearsal off
    the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import paged_attention as pa

    bs = 16
    full = table_blocks * bs
    lens = [min(n, full) for n in (0, 1, bs, bs + 1, full, 300, 640, 255,
                                   256, 257, 0, 5, 900, 511, 512, 513)]
    lens = lens[:slots]
    assert len(lens) == slots, f"at most {len(lens)} slots"
    blocks = 1 + slots * table_blocks
    keys = jax.random.split(jax.random.key(SEED + 3), 3)
    shape = (layers, blocks, bs, heads * head_dim)
    k_pool = jax.random.normal(keys[0], shape, jnp.bfloat16)
    v_pool = jax.random.normal(keys[1], shape, jnp.bfloat16)
    q = jax.random.normal(keys[2], (slots, heads * head_dim), jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    free = list(rng.permutation(np.arange(1, blocks)))
    tables = np.zeros((slots, table_blocks), np.int32)
    for s, n in enumerate(lens):
        tables[s, :-(-n // bs)] = [free.pop() for _ in range(-(-n // bs))]
    tables = jnp.asarray(tables)
    positions = jnp.asarray([max(n - 1, 0) for n in lens], jnp.int32)

    def over_layers(attend):
        return jax.jit(lambda q, kp, vp: jax.lax.map(
            lambda l: attend(q, kp, vp, l, tables, positions),
            jnp.arange(layers, dtype=jnp.int32)))

    paged = over_layers(lambda *a: pa.paged_attention(
        *a, heads=heads, interpret=interpret))
    def gathered(q, kp, vp, l, bt, pos):    # one query row a slot
        return decoder.cached_attention(
            lambda *a: decoder.mha_cached(*a, heads), q[:, None], kp, vp, l,
            bt, pos[:, None])[:, 0]

    gather = over_layers(gathered)
    (got, want), compile_s = _timed(
        lambda: (paged(q, k_pool, v_pool), gather(q, k_pool, v_pool)))
    _, paged_s = _timed(lambda: paged(q, k_pool, v_pool))
    _, gather_s = _timed(lambda: gather(q, k_pool, v_pool))
    live = np.asarray(lens) > 0
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.abs(got - want)[:, live]
    assert np.isfinite(got).all()
    assert diff.max() <= tol, (
        f"the kernel's context leaves the gather path's by up to "
        f"{diff.max():.4f} (tolerance {tol}) at {heads} heads of {head_dim}")
    info.update(compile_s=round(compile_s, 2),
                run_s=round(paged_s + gather_s, 4),
                checked={"heads": heads, "head_dim": head_dim,
                         "layers": layers, "lens": lens,
                         "max_abs_diff": float(f"{diff.max():.3g}"),
                         "tol": tol,
                         "paged_ms": round(1e3 * paged_s, 3),
                         "gather_ms": round(1e3 * gather_s, 3)})
    return info


def short_attention_phase(info: dict, batch: int = 256, seq: int = 128,
                          heads: int = 12, head_dim: int = 64,
                          tol: float = 0.01, interpret=False) -> dict:
    """The fused short-sequence attention kernel (`attention._short_mha`,
    BERT's route since PR 54) against `_xla_mha` in FLOAT32 on one device,
    at the training cells' shape: unit-normal bf16 q, k, v and cotangent;
    the context and all three gradients by relative L2 norm. `tol` 0.01:
    the kernel rounds q, k, v, the probabilities and dS to bf16 once each
    (2^-9 relative a rounding; on the chip the context reads 0.17% and the
    gradients 0.06-0.07%, PERF.md section 6, PR 54) and the XLA route in
    bf16, which also rounds its scores, stands at 0.45% from the same
    reference; a lost head, a wrong scale or a missing term of dS reads
    10% and more. `interpret` is for the rehearsal off the chip."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import attention

    shape = (batch, seq, heads, head_dim)
    q, k, v, ct = (jax.random.normal(key, shape, jnp.bfloat16)
                   for key in jax.random.split(jax.random.key(SEED + 5), 4))
    scale = head_dim ** -0.5

    def both(attend, dtype):
        def loss(q, k, v):
            out = attend(q.astype(dtype), k.astype(dtype), v.astype(dtype))
            return (out * ct).astype(jnp.float32).sum(), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    short = both(lambda q, k, v: attention._short_mha(
        q, k, v, scale, interpret=interpret), jnp.bfloat16)
    ((_, got), got_g), compile_s = _timed(lambda: short(q, k, v))
    _, run_s = _timed(lambda: short(q, k, v))
    (_, want), want_g = both(lambda q, k, v: attention._xla_mha(
        q, k, v, None, scale), jnp.float32)(q, k, v)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    diffs = {name: rel(a, b) for name, a, b in zip(
        ("context", "dq", "dk", "dv"), (got, *got_g), (want, *want_g))}
    assert all(d <= tol for d in diffs.values()), (
        f"the short kernel leaves float32 attention by {diffs} "
        f"(tolerance {tol}) at {shape}")
    info.update(compile_s=round(compile_s, 2), run_s=round(run_s, 4),
                checked={"shape": list(shape), "tol": tol,
                         "rel_l2": {k: float(f"{d:.3g}")
                                    for k, d in diffs.items()},
                         "fwd_bwd_ms": round(1e3 * run_s, 3)})
    return info


def longcat_experts_phase(info: dict, cfg, rows=(128, 512), layer: int = 1,
                          tol: float = 0.03) -> dict:
    """LongCat's expert path ALONE against the plain reference's
    (`_held_experts_phase`, under the scope `shortcut_experts`; a decode
    step's 128 rows, the largest prefill bucket's 512)."""
    from benchmarks.reference import longcat_ref
    from paddle_tpu.models import longcat

    model = {"n_experts": cfg.n_experts, "top_k": cfg.top_k,
             "route_scale": cfg.route_scale,
             "held": list(cfg.routing.held_range)}
    return _held_experts_phase(
        info, cfg, rows, layer, tol, model=model, scope=longcat.SCOPE,
        experts=longcat._EXPERTS, ref_experts=longcat_ref.experts,
        weights=lambda lp, y: longcat_ref.route(lp, y, model),
        make_layer=lambda key, l: longcat.init_layer(key, cfg, l))


def granite_experts_phase(info: dict, cfg, rows=(48, 1024), layer: int = 1,
                          tol: float = 0.03) -> dict:
    """Granite 4.0-H's expert layer ALONE against the plain reference's
    (`_held_experts_phase`, under the scope `mlp`): a decode step's 48 rows
    (480 pairs, not whole row tiles of the grouped matmul: filled up) and a
    prompt slice's 1024; the shared expert is in both sides."""
    from benchmarks.reference import granite_hybrid_ref
    from paddle_tpu.models import granite_hybrid

    model = {"n_experts": cfg.n_experts, "top_k": cfg.top_k,
             "held": list(cfg.routing.held_range)}
    return _held_experts_phase(
        info, cfg, rows, layer, tol, model=model, scope="mlp",
        experts=tuple("blk." + k for k in granite_hybrid._EXPERTS),
        ref_experts=granite_hybrid_ref.experts,
        weights=lambda lp, y: granite_hybrid_ref.route(
            y @ lp["blk.router"], model),
        # layer l's experts are block 2l + 1
        make_layer=lambda key, l: granite_hybrid.init_layer(
            key, cfg, 2 * l + 1, "E"))


def trinity_experts_phase(info: dict, cfg, rows=(32, 1024), layer: int = 1,
                          tol: float = 0.03) -> dict:
    """Trinity's expert layer ALONE against the plain reference's
    (`_held_experts_phase`, under the scope `mlp`): a decode step's 32 rows
    (256 pairs, about 2 a held expert) and a prompt slice's 1024; sigmoid
    scores, a bias that selects, the kept weights normalised and scaled;
    the shared expert is in both sides."""
    import jax

    from benchmarks.reference import afmoe_ref
    from paddle_tpu.models import afmoe

    model = {"n_experts": cfg.n_experts, "top_k": cfg.top_k,
             "route_scale": cfg.route_scale,
             "held": list(cfg.routing.held_range)}
    return _held_experts_phase(
        info, cfg, rows, layer, tol, model=model, scope="mlp",
        experts=tuple("blk." + k for k in afmoe._EXPERTS),
        ref_experts=afmoe_ref.experts,
        weights=lambda lp, y: afmoe_ref.route(
            jax.nn.sigmoid(y @ lp["blk.router"]), lp["blk.router_bias"],
            model),
        # expert layer l is block 2 (dense_layers + l) + 1
        make_layer=lambda key, l: afmoe.init_layer(
            key, cfg, 2 * (cfg.dense_layers + l) + 1, "moe"))


def _held_experts_phase(info: dict, cfg, rows, layer: int, tol: float, *,
                        model, scope, experts, ref_experts, weights,
                        make_layer) -> dict:
    """A model's expert path ALONE against its plain reference's, at the
    widths of `cfg`: `moe.expert_mlp` as the serve programs call it (bf16,
    the held experts' stacks of `layer + 1` layers addressed in place at
    `layer`, under `scope`) for `rows` unit-normal rows against
    `ref_experts` in float32 on the SAME bf16-rounded weights and rows, so
    that both routers see the same logits. What is compared is the HELD
    experts' part by itself: the program's result less the reference's
    with the held term dropped, against the reference's held term, row by
    row where a row has a pair on a held expert (a relative L2 distance of
    `tol` at most: the matmuls round to bf16 twice), and the rows with none
    against the reference outright. A cell's `correct` sees this term
    through its layers and a head; here nothing stands between the grouped
    matmuls' tiles and the verdict. `make_layer(key, l)` gives layer l's
    expert parameters in float32, `weights(lp, y)` the reference's `[rows,
    router outputs]` weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import moe
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    key = jax.random.key(SEED + 5)
    make = jax.jit(lambda l: {
        k: v.astype(jnp.bfloat16) if v.ndim >= 2 else v
        for k, v in make_layer(key, l).items()
        if k.split(".")[1].startswith(("router", "shared", "w_"))})
    made = [make(np.int32(l)) for l in range(layer + 1)]
    served = dict(made[layer], **{
        k: jnp.stack([lp[k] for lp in made]) for k in experts})
    exact = {k: v.astype(jnp.float32) for k, v in made[layer].items()}
    del made
    first, past = model["held"]
    run = jax.jit(lambda lp, y: moe.expert_mlp(
        lp, y, cfg.routing, np.int32(layer), scope=scope))
    gmm.GATE_COUNTS.clear()
    gmm.TILES.clear()
    checked = {"rows": {}}
    for n in rows:
        y = jax.random.normal(jax.random.fold_in(key, n), (n, cfg.hidden),
                              jnp.bfloat16)
        got, stats = run(served, y)
        with jax.default_matmul_precision("highest"):
            y32 = y.astype(jnp.float32)
            w = np.asarray(weights(exact, y32))
            whole = np.asarray(ref_experts(exact, y32, model))
            bare = np.asarray(ref_experts(
                exact, y32, dict(model, held_term=False)))
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        picked = w > 0
        assert int(stats["held_pairs"]) == picked[:, first:past].sum()
        assert int(stats["zero_pairs"]) == picked[:, cfg.n_experts:].sum()
        held = picked[:, first:past].any(-1)
        assert held.sum() >= n // 8, "too few rows reach a held expert"
        term = (whole - bare)[held]
        norm = np.linalg.norm(term, axis=-1)
        away = np.linalg.norm((got - bare)[held] - term, axis=-1) / norm
        # a row with no held pair: the zero experts' scale alone, rounded
        rest = np.abs(got - whole)[~held].max() if (~held).any() else 0.0
        assert away.max() <= tol, (
            f"the held experts' term leaves the reference's by "
            f"{away.max():.4f} of its norm at {n} rows (tolerance {tol})")
        assert rest <= tol * np.abs(whole).max(), rest
        checked["rows"][str(n)] = {
            "held_rows": int(held.sum()),
            "held_pairs": int(stats["held_pairs"]),
            "experts_hit": int(stats["experts_hit"]),
            "held_term_rms": float(f"{np.sqrt((term ** 2).mean()):.3g}"),
            "m_rms": float(f"{np.sqrt((whole ** 2).mean()):.3g}"),
            "max_rel_l2": float(f"{away.max():.3g}"),
            "other_rows_max_abs": float(f"{rest:.3g}")}
    checked.update(
        tol=tol, routes=dict(gmm.GATE_COUNTS),
        tiles={f"{k}x{n}": list(t) for (k, n), t in sorted(gmm.TILES.items())})
    info.update(checked=checked)
    return info


def sharded_phase(info: dict, cfg, batch_size: int, seq_len: int,
                  devices, steps: int = 5, rel_tol: float = 1e-2) -> dict:
    """BERT under make_mesh(dp=-1, tp=2) over `devices` against the same
    batch on a one-device mesh in this process: per-step losses agree
    within `rel_tol` (bf16 activations: 2^-8 per rounding, averaged over
    the batch's tokens), and the sharded state really is spread."""
    from paddle_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=-1, tp=2), devices=devices)
    sub, sharded, state, text = train_phase({}, cfg, batch_size, seq_len,
                                            mesh, steps=steps - 1,
                                            want_text=True)
    assert "all-reduce" in text, "no collective in the sharded step"
    holders = set()
    for leaf in state.params.values():
        holders |= {s.device for s in leaf.addressable_shards}
    assert holders == set(devices), \
        f"parameters live on {holders}, not on all of {devices}"
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices]
    del state
    one = make_mesh(MeshConfig(dp=-1), devices=devices[:1])
    _, single, state, _ = train_phase({}, cfg, batch_size, seq_len, one,
                                      steps=steps - 1)
    del state
    worst = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    assert worst <= rel_tol, (
        f"sharded vs one-device loss differs by {worst:.2e} relative "
        f"(tolerance {rel_tol}): {sharded} vs {single}")
    info.update(compile_s=sub["compile_s"], run_s=sub["run_s"],
                step_ms=sub["step_ms"],
                checked={"mesh": {a: int(n) for a, n in mesh.shape.items()
                                  if n > 1},
                         "losses_sharded": [round(x, 4) for x in sharded],
                         "losses_one_device": [round(x, 4)
                                               for x in single],
                         "loss_rel_diff_max": float(f"{worst:.3g}"),
                         "loss_rel_tol": rel_tol,
                         "param_shard_devices": len(holders),
                         "bytes_in_use": in_use})
    return info


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------


def run_one_chip() -> None:
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import (afmoe, bert, gpt, granite_hybrid, jamba,
                                   joyai,
                                   longcat, nemotron_h, olmoe, xing4)
    from paddle_tpu.parallel import MeshConfig, make_mesh
    from paddle_tpu.serving.decode import DecodeConfig

    mesh = make_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])

    with phase("program") as info:
        program_phase(info, pt.TPUPlace(0))
        assert info["checked"]["loss_last"] < \
            0.7 * info["checked"]["loss_first"], info

    with phase("train") as info:
        # BERT-base, bs 256 (no batch ladder), seq 128: the bert_base cell
        train_phase(info, bert.BertConfig.base(), 256, 128, mesh, steps=5)

    with phase("long_seq") as info:
        # the bert_long_seq4096 cell, flag `auto`: the gate must pick the
        # splash kernel and the kernel must be IN the compiled step
        _, _, _, text = train_phase(
            info, bert.BertConfig(max_len=4096, dropout=0.0), 8, 4096,
            mesh, steps=4, deterministic=True, want_text=True)
        gate = info["checked"]["gate"]
        assert gate.get("splash", 0) >= 1 and gate.get("xla", 0) == 0, gate
        assert "tpu_custom_call" in text, \
            "no Pallas kernel in the compiled long-sequence step"
        info["checked"]["tpu_custom_call"] = True

    # GPT at its dataclass defaults (768/12/12/3072, vocab 50304, context
    # 1024, bf16); 16 slots x 64 blocks of 16 tokens = every slot can hold
    # a full context. Prompt buckets are the default pow2 grid to 1024.
    cfg = gpt.GPTConfig()
    rng = np.random.RandomState(SEED)
    # 600 tokens pad to the 1024 bucket, the one whole-prompt prefill
    # shape where the auto gate takes the splash kernel
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (12, 509, 5, 512, 40, 498, 600)]
    with phase("serve") as info:
        # tolerance: the engine computes in bf16 (2^-8 per rounding) and
        # logits here are O(1) sums over 768 features and 12 layers of
        # such roundings; 0.25 is far below the ~2.4 logits that separate
        # the argmax from a wrong token
        serve_phase(info, cfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(8, 16)),
            prompts, max_new=24, logit_tol=0.25)
        # both decode programs read the live blocks through the table
        assert info["checked"]["decode_attention"] == {"paged": 2}, info
        # K and V of eight prefill programs: the bucket of 8 is under a
        # block, the seven of 16 to 1024 go in a block at a time
        assert info["checked"]["prefill_write"] == {"rows": 2,
                                                    "blocks": 14}, info

    with phase("serve_reuse") as info:
        reuse_phase(info, cfg, DecodeConfig(
            block_size=16, num_blocks=4 * 64 + 1, decode_slots=(4,),
            prefill_chunk=128, prefix_cache=True),
            rng.randint(0, cfg.vocab_size, 300).tolist(), max_new=16)
        assert info["checked"]["decode_attention"] == {"paged": 1}, info

    # OLMoE-1B-7B at its published widths (2048 wide, 16 heads of 128, 64
    # experts of 1024, 8 a token, vocab 50304), 2 of its 16 layers so that
    # the float32 set for the reference (4.2 GB) sits beside the served
    # one: the same engine, loop, allocator and pool through the model
    # interface (models/decoder.py), 16 slots of 1024 tokens
    ocfg = olmoe.OlmoeConfig(layers=2, max_len=1024)
    prompts = [rng.randint(0, ocfg.vocab_size, n).tolist()
               for n in (12, 200, 5, 64, 40, 250, 129)]
    with phase("serve_olmoe") as info:
        # tolerance: that of the benchmark's cell (benchmarks/configs/
        # olmoe_1b_7b.json says what it tells apart)
        serve_phase(info, ocfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(16,),
            prefill_buckets=(64, 128, 256)), prompts, max_new=24,
            logit_tol=OLMOE_LOGIT_TOL, model=olmoe,
            reference_gaps=_olmoe_reference_gaps)
        assert info["checked"]["decode_attention"] == {"paged": 1}, info
        assert info["checked"]["prefill_write"] == {"blocks": 6}, info

    # JoyAI-LLM-Flash at its published widths (latent attention: 32 heads
    # over a 512 + 64 cache row; top-8 of sigmoid-routed experts of 768 and
    # a shared one; vocab 129280), the dense layer and ONE expert layer of
    # 64 of the 256 experts, so that the float32 set for the reference
    # (3.7 GB) sits beside the served one: the model whose cache layout,
    # decode attention and leading layer are its own
    jcfg = joyai.JoyaiConfig(layers=2, n_experts=64, max_len=1024)
    prompts = [rng.randint(0, jcfg.vocab_size, n).tolist()
               for n in (12, 200, 5, 64, 40, 250, 129)]
    with phase("serve_joyai") as info:
        serve_phase(info, jcfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(16,),
            prefill_buckets=(64, 128, 256)), prompts, max_new=24,
            logit_tol=JOYAI_LOGIT_TOL, model=joyai,
            reference_gaps=_joyai_reference_gaps)
        # the latent kernel, not the gathered form: a closed gate would
        # serve the same tokens slower and nothing else would say so
        assert info["checked"]["decode_attention"] == {"paged_latent": 1}, \
            info
        # the leading dense layer is traced beside the scanned one: two
        # layer bodies a prefill program, K and V (here `c` and the rotary
        # key) each, three programs
        assert info["checked"]["prefill_write"] == {"blocks": 12}, info

    # Xing4.0-29B-A4B at its published widths (four residual streams of
    # 3584 mixed by Sinkhorn-projected maps around JoyAI's block: latent
    # attention with YaRN, top-4 of sigmoid-routed experts of 1024 and a
    # shared one; vocab 131072), ONE dense and ONE expert layer of 16 of the
    # 64 experts, so that the float32 set for the reference (5.2 GB) sits
    # beside the served one: a prompt in one slice of 2048 (the 2048
    # bucket), prompts walked in two slices over the latent cache (the 4096
    # bucket), and 16 decode steps each through the latent kernel
    xcfg = xing4.Xing4Config(layers=2, dense_layers=1, n_experts=16,
                             max_len=4352)
    prompts = [rng.randint(0, xcfg.vocab_size, n).tolist()
               for n in (12, 200, 2100, 3000, 40, 2048)]
    with phase("serve_xing4") as info:
        serve_phase(info, xcfg, DecodeConfig(
            block_size=16, num_blocks=8 * 272 + 1, decode_slots=(8,),
            prefill_buckets=(2048, 4096)), prompts, max_new=16,
            logit_tol=XING4_LOGIT_TOL, model=xing4,
            reference_gaps=_xing4_reference_gaps)
        assert info["checked"]["decode_attention"] == {"paged_latent": 1}, \
            info
        # two layer bodies a prefill program, `c` and the rotary key each,
        # two programs
        assert info["checked"]["prefill_write"] == {"blocks": 8}, info

    # Nemotron-3-Nano at its published widths (Mamba-2: 64 heads of 64, 8
    # groups, state 128; 32 query heads over 2 K/V heads of 128, no
    # positions; top-6 of relu^2 experts of 1856 and a shared one of 3712;
    # vocab 131072), blocks 3 to 7 of its pattern (`MEM*E`: two Mamba-2,
    # two expert, the attention block) with 32 of the 128 experts, so that
    # the float32 set for the reference (5.9 GB) sits beside the served
    # one: the model whose blocks hold one mixer each and whose sequences
    # keep a state row beside their blocks
    ncfg = nemotron_h.NemotronHConfig(pattern="MEM*E", n_experts=32,
                                      max_len=1024)
    prompts = [rng.randint(0, ncfg.vocab_size, n).tolist()
               for n in (12, 200, 5, 64, 40, 250, 129)]
    with phase("serve_nemotron") as info:
        serve_phase(info, ncfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(16,),
            prefill_buckets=(64, 128, 256)), prompts, max_new=24,
            logit_tol=NEMOTRON_LOGIT_TOL, model=nemotron_h,
            reference_gaps=_nemotron_reference_gaps)
        checked = info["checked"]
        # the 16 query heads of a K/V head through the kernel, and the
        # state rows advanced where they lie: closed gates would serve the
        # same tokens slower and nothing else would say so
        assert checked["decode_attention"] == {"paged_gqa": 1}, info
        assert checked["state"]["update"] == {"kernel": 2}, info
        # the experts of the three prefill programs and of the decode
        # program (16 slots x 6 experts are 96 rows: filled up to a row
        # tile since PR 58) through the megablox kernel, their columns whole
        assert checked["expert_matmul"]["routes"] == {"megablox": 16}, info
        assert checked["expert_matmul"]["tiles"] == {
            "1920x2688": [128, 640, 2688],
            "2688x1920": [128, 896, 1920]}, info
        assert checked["state"]["rows"] == 16 \
            and checked["state"]["used"] == 0, info
        # one attention block: K and V, three prefill programs
        assert checked["prefill_write"] == {"blocks": 6}, info

    # AI21-Jamba2-3B at its published widths (Mamba-1: 5120 channels of 16
    # state lanes, dt of rank 160, norms inside the mixer; 20 query heads
    # over ONE K/V head of 128, no positions; dense SwiGLU of 8192; the
    # embedding of 65536 rows read as the head), four layers with the
    # attention layer second (`ME*EMEME`), so that the float32 set for the
    # reference (2.1 GB) sits beside the served one: the selective
    # recurrence's rows advanced where they lie, and multi-query attention
    # through the table
    jcfg = jamba.JambaConfig(n_layers=4, attn_period=4, attn_offset=1,
                             max_len=1024)
    prompts = [rng.randint(0, jcfg.vocab_size, n).tolist()
               for n in (12, 200, 5, 64, 40, 250, 129)]
    with phase("serve_jamba") as info:
        serve_phase(info, jcfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(16,),
            prefill_buckets=(64, 128, 256)), prompts, max_new=24,
            logit_tol=JAMBA_LOGIT_TOL, model=jamba,
            reference_gaps=_jamba_reference_gaps)
        checked = info["checked"]
        # the 20 query heads of the one K/V head through the kernel (its
        # query block filled up to 32 rows), and the three Mamba layers'
        # rows, states and tails, advanced where they lie
        assert checked["decode_attention"] == {"paged_gqa": 1}, info
        # (and a prompt's scan with its state in VMEM: three Mamba layers
        # in each of three prefill programs)
        assert checked["state"]["update"] == {
            "kernel": 3, "tail_kernel": 3, "scan_kernel": 9}, info
        assert checked["state"]["rows"] == 16 \
            and checked["state"]["used"] == 0, info
        # one attention layer: K and V, three prefill programs
        assert checked["prefill_write"] == {"blocks": 6}, info

    # LongCat-Flash-Chat at its published widths (a layer of TWO latent
    # attention sub-layers of 64 heads and two dense SwiGLUs of 12288, the
    # expert path a shortcut across the second; a router over 768 outputs,
    # 256 of them zero-compute, top-12), two layers = four cache layers
    # with 2 of the 512 routed experts held and an eighth of the
    # vocabulary, so that the float32 set for the reference (5.7 GB) sits
    # beside the served one: the latent kernel at 64 heads, the grouped
    # matmuls at 6144 x 2048 over a share of the experts
    lcfg = longcat.LongcatConfig(layers=2, vocab_size=16384, held=(0, 2),
                                 max_len=1024)
    prompts = [rng.randint(0, lcfg.vocab_size, n).tolist()
               for n in (12, 200, 5, 64, 40, 250, 129)]
    with phase("serve_longcat") as info:
        serve_phase(info, lcfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(16,),
            prefill_buckets=(64, 128, 256)), prompts, max_new=24,
            logit_tol=LONGCAT_LOGIT_TOL, model=longcat,
            reference_gaps=_longcat_reference_gaps)
        checked = info["checked"]
        assert checked["decode_attention"] == {"paged_latent": 1}, info
        # the held experts through the megablox kernel, their columns
        # whole (the decode program's 16 slots x 12 picks are 192 rows,
        # filled up to whole row tiles)
        assert checked["expert_matmul"]["tiles"] == {
            "6144x2048": [128, 768, 2048],
            "2048x6144": [128, 256, 6144]}, info
        # two sub-blocks a layer body, `c` and the rotary key each, three
        # prefill programs
        assert checked["prefill_write"] == {"blocks": 12}, info

    # the expert path alone at the cell's widths and share (16 of 512
    # held, 768 router outputs, top-12) against the reference's, the held
    # experts' term by itself: what the cell's `correct` sees only through
    # four layers and a head
    with phase("longcat_experts") as info:
        longcat_experts_phase(info, longcat.LongcatConfig(
            layers=2, vocab_size=16384, held=(0, 16), max_len=1024))
        checked = info["checked"]
        # three matmuls a call, both row counts through the kernel
        assert checked["routes"] == {"megablox": 6}, info
        assert checked["tiles"] == {
            "6144x2048": [128, 768, 2048],
            "2048x6144": [128, 256, 6144]}, info

    # Granite-4.0-H-Small at its published widths (every layer a mixer
    # then top-10 of 72 SwiGLU experts under the four multipliers; 128
    # Mamba-2 heads on ONE B/C group, 32Q/8KV attention at 1/128), layers
    # 4 to 6 of its pattern (`M*M`) with 8 of the 72 experts held and an
    # eighth of the vocabulary, prompts walked in slices of 128: the state
    # update's kernel where a block of heads is PART of the one group, the
    # grouped-query kernel told the model's scale, and 160 pairs a decode
    # step filled up to whole row tiles
    gcfg = granite_hybrid.GraniteHybridConfig(
        pattern="M*M", held=(0, 8), vocab_size=12544, prompt_slice=128,
        max_len=1024)
    prompts = [rng.randint(0, gcfg.vocab_size, n).tolist()
               for n in (12, 200, 5, 64, 40, 250, 129)]
    with phase("serve_granite") as info:
        serve_phase(info, gcfg, DecodeConfig(
            block_size=16, num_blocks=16 * 64 + 1, decode_slots=(16,),
            prefill_buckets=(128, 256)), prompts, max_new=24,
            logit_tol=GRANITE_LOGIT_TOL, model=granite_hybrid,
            reference_gaps=_granite_reference_gaps)
        checked = info["checked"]
        assert checked["decode_attention"] == {"paged_gqa": 1}, info
        assert checked["state"]["update"] == {"kernel": 2}, info
        # three matmuls an expert layer, three layers, three programs
        assert checked["expert_matmul"]["routes"] == {"megablox": 27}, info

    # the expert layer alone at the cell's widths and share (36 of 72 held,
    # top-10) against the reference's, the held experts' term by itself
    with phase("granite_experts") as info:
        granite_experts_phase(info, granite_hybrid.GraniteHybridConfig(
            pattern="MM", held=(0, 36), vocab_size=12544))
        assert info["checked"]["routes"] == {"megablox": 6}, info

    # Trinity-Mini at its published widths (32Q/4KV attention behind a
    # sigmoid output gate and between two norms; `W*`: a sliding-window
    # layer of 2048 keys with rotary positions over the WINDOW kind's ring,
    # a full layer without positions over the global kind; a dense MLP,
    # then 8 held of 128 sigmoid-routed experts), an eighth of the
    # vocabulary, prompts walked in slices of 1024 to 4096 tokens and
    # decoded past the ring's wrap (3088 tokens): both kinds through the
    # grouped-query kernel, the window kind's from its window's first block
    tcfg = afmoe.AfmoeConfig(
        pattern="W*", dense_layers=1, held=(0, 8), vocab_size=12512,
        max_len=8192)
    prompts = [rng.randint(0, tcfg.vocab_size, n).tolist()
               for n in (700, 3000, 4096, 2500)]
    with phase("serve_trinity") as info:
        serve_phase(info, tcfg, DecodeConfig(
            block_size=16, num_blocks=4 * 512 + 1, decode_slots=(4,),
            prefill_buckets=(1024, 4096)), prompts, max_new=40,
            logit_tol=TRINITY_LOGIT_TOL, model=afmoe,
            reference_gaps=_trinity_reference_gaps)
        checked = info["checked"]
        assert checked["decode_attention"] == {
            "paged_gqa": 1, "paged_gqa_window": 1}, info
        # three matmuls, one expert layer, three programs
        assert checked["expert_matmul"]["routes"] == {"megablox": 9}, info

    # the expert layer alone at the cell's widths and share (64 of 128
    # held, top-8) against the reference's, the held experts' term by itself
    with phase("trinity_experts") as info:
        trinity_experts_phase(info, afmoe.AfmoeConfig(
            pattern="WW*", dense_layers=1, held=(0, 64), vocab_size=12512))
        assert info["checked"]["routes"] == {"megablox": 6}, info

    # Mosaic is not run by the CPU tests: the short kernel alone at the
    # training cells' shape against float32 attention
    with phase("short_attention") as info:
        short_attention_phase(info)

    # the kernel against the gather path where it runs, at the benchmark's
    # two widths: GPT-2-large's 20 heads of 64, OLMoE's 16 of 128
    for heads, head_dim, layers in ((20, 64, 4), (16, 128, 4)):
        with phase(f"paged_attention_{heads}x{head_dim}") as info:
            paged_attention_phase(info, heads, head_dim, layers)


def run_four_chips() -> None:
    import jax

    from paddle_tpu.models import bert

    devices = jax.devices()
    assert len(devices) == 4, f"--chips 4 needs four devices: {devices}"
    with phase("sharded_train") as info:
        sharded_phase(info, bert.BertConfig.base(), 256, 128, devices)
        assert all(info["checked"]["bytes_in_use"]), \
            f"a chip holds nothing: {info['checked']['bytes_in_use']}"

    with phase("compositions") as info:
        # dp x tp x sp BERT, ring-splash under sp (kernels compiled: the
        # devices are TPUs), pp x ep MoE, conv+BN dp parity
        import __graft_entry__ as graft

        from paddle_tpu.ops.pallas import attention

        _, seconds = _timed(lambda: graft._dryrun_multichip_impl(4))
        gate = dict(attention.GATE_COUNTS)
        assert gate.get("ring_splash", 0) >= 1, gate
        info.update(compile_s=None, run_s=round(seconds, 2),
                    checked={"gate": gate})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip path (needs a "
                         "four-chip host); default 1")
    args = ap.parse_args(argv)

    ok = False
    device = {"platform": None, "kind": None, "count": 0}
    try:
        import jax

        from paddle_tpu.core.compile_cache import place_jax_cache

        cache_dir = place_jax_cache()
        _listen()
        with phase("device") as info:
            device_phase(info)
            info["jax_cache_dir"] = cache_dir
            info["jax"] = jax.__version__
        device = {k: info[k] for k in ("platform", "kind", "count")}
        if device["platform"] != "tpu":
            raise RuntimeError(
                f"no TPU: JAX's devices are {device} — this script "
                "proves the chip path and has no other")
        if info["peaks_row"] is None:
            raise RuntimeError(
                f"device_kind {device['kind']!r} matches no row of "
                "observability/device_peaks.PEAKS")
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
        ok = True
    finally:
        # the one line the driver reads; a failure's traceback follows it
        # on stderr and the exit code is non-zero
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
