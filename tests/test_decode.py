"""Continuous-batching decode engine (ISSUE 12, SERVING.md
§Continuous batching): paged KV block allocator, prefill/decode phase
split, in-flight batching semantics, streaming HTTP and warmstart grid
replay.

The load-bearing correctness claims pinned here:

- the paged decode step computes EXACTLY what the full-context forward
  computes (block-table attention == causal attention over the grown
  sequence);
- decode math is row-isolated, so a sequence's tokens are bit-identical
  whatever else shares the batch (admit-mid-decode == solo decode) —
  the property that makes continuous batching transparent to clients;
- blocks scale with live tokens: finished sequences return every block,
  pool pressure preempts-and-replays without changing emitted tokens;
- a warmstart-booted engine replays the whole phase grid with ZERO
  fresh compile events and bit-identical first tokens vs a cold boot.
"""

import functools
import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

import paddle_tpu  # noqa: F401 — package init registers telemetry
from benchmarks.reference import gpt_ref
from paddle_tpu import observability
from paddle_tpu.models import gpt
from paddle_tpu.observability import events
from paddle_tpu.serving import (DecodeConfig, DecodeEngine, QueueFullError,
                                Server, ServingConfig)
from paddle_tpu.serving.kv_cache import (BlockAllocator, KVCacheConfig,
                                         NoBlocksError, build_block_table,
                                         gather_kv, init_pools,
                                         write_prefill_kv, write_token_kv)

from serve_contract import Family, ServeContract, seeded

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def _tiny():
    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"  # exactness vs the full-forward reference
    return cfg, seeded(gpt, cfg)


@pytest.fixture(scope="module")
def model():
    cfg, params = _tiny()
    return params, cfg


def make_engine(model, **kw):
    params, cfg = model
    base = dict(block_size=8, num_blocks=64, decode_slots=(4,),
                prefill_buckets=(8,), precision="f32", max_len=64)
    base.update(kw)
    return DecodeEngine(params, cfg, DecodeConfig(**base))


@pytest.fixture(scope="module")
def engine(model):
    eng = make_engine(model)
    eng.warmup()
    yield eng
    eng.stop()


# ---------------------------------------------------------------------------
# What every served family must do (tests/serve_contract.py), for GPT-2: the
# family whose engine tests are this file's. Its programs have no scope and
# no counter beside the shared ones.
# ---------------------------------------------------------------------------

FAMILY = Family(
    module=gpt, tiny=_tiny, ref=gpt_ref,
    ref_model=lambda cfg: {"heads": cfg.heads, "layers": cfg.layers},
    gaps=gpt_ref.stream_gaps,
    tol=5e-5, tol_why="float32 on both sides: rounding on logits whose "
                      "deviation is 0.16 (the tied embedding at the init's "
                      "0.02)",
    faults=(("one_layer_fewer", {"layers": 3}),
            ("two_heads_for_four", {"heads": 2})))


class TestContract(ServeContract):
    family = FAMILY


# ---------------------------------------------------------------------------
# Block allocator + pool helpers
# ---------------------------------------------------------------------------


def test_block_allocator_units():
    cfg = KVCacheConfig(layers=2, kv_heads=2, head_dim=4, max_len=32,
                        block_size=8, num_blocks=6)
    al = BlockAllocator(cfg)
    assert al.free_blocks() == 5          # block 0 reserved (null)
    got = al.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert al.used_blocks() == 3 and al.free_blocks() == 2
    # exhaustion refuses WITHOUT a partial grant
    with pytest.raises(NoBlocksError):
        al.alloc(3)
    assert al.free_blocks() == 2
    al.free(got[:1])
    assert al.free_blocks() == 3
    # double free and null-block free are programming errors
    with pytest.raises(ValueError):
        al.free(got[:1])
    with pytest.raises(ValueError):
        al.free([0])
    # fragmentation accounting: 2 blocks allocated, 9 live tokens ->
    # capacity 16, waste 7
    al2 = BlockAllocator(cfg)
    al2.alloc(2)
    st = al2.stats(live_tokens=9)
    assert st["allocated_token_capacity"] == 16
    assert st["internal_waste_tokens"] == 7
    assert st["waste_fraction"] == round(7 / 16, 4)


def test_allocator_rejects_degenerate_pool():
    with pytest.raises(ValueError):
        BlockAllocator(KVCacheConfig(layers=1, kv_heads=1, head_dim=2,
                                     max_len=8, block_size=8,
                                     num_blocks=1))


def test_kv_pool_write_gather_roundtrip():
    cfg = KVCacheConfig(layers=2, kv_heads=2, head_dim=3, max_len=16,
                        block_size=4, num_blocks=5, dtype="float32")
    pool, _ = init_pools(cfg)
    assert pool.shape == (2, 5, 4, 2 * 3)          # [L, NB, BS, H*D]
    layer = np.int32(1)
    # prefill a 6-token sequence into blocks [1, 2] of layer 1
    kv = np.arange(6 * 6, dtype=np.float32).reshape(6, 6)
    bt = build_block_table([1, 2], cfg.max_blocks_per_seq)
    pool = write_prefill_kv(pool, layer, kv, bt, cfg.block_size)
    ctx = gather_kv(pool, layer, bt[None])         # [1, MB*BS, H*D]
    np.testing.assert_array_equal(np.asarray(ctx)[0, :6], kv)
    # decode-step write at position 6 (block 1 of the table, slot 2)
    tok = np.full((1, 6), 7.0, np.float32)
    pool = write_token_kv(pool, layer, tok, bt[None],
                          np.array([6], np.int32), cfg.block_size)
    ctx = gather_kv(pool, layer, bt[None])
    np.testing.assert_array_equal(np.asarray(ctx)[0, 6], tok[0])
    # untouched tail stays zero, and so does the other layer
    assert float(np.abs(np.asarray(ctx)[0, 7:8]).sum()) == 0.0
    assert float(np.abs(np.asarray(pool)[0]).sum()) == 0.0


def test_build_block_table_bounds():
    row = build_block_table([3, 4], 4)
    np.testing.assert_array_equal(row, [3, 4, 0, 0])
    with pytest.raises(ValueError):
        build_block_table([1, 2, 3], 2)


# ---------------------------------------------------------------------------
# Decode correctness
# ---------------------------------------------------------------------------


def test_decode_matches_full_forward(model, engine):
    """The paged decode path (prefill + block-table attention steps)
    must produce exactly the greedy tokens of the naive recompute-
    everything forward — same floats, same argmax, every step."""
    params, cfg = model
    prompt = [1, 2, 3, 4, 5]
    got = engine.submit(prompt, max_new_tokens=6).result(timeout_s=120)
    seq = list(prompt)
    want = []
    for _ in range(6):
        ids = np.asarray(np.array(seq, np.int32)[None])
        logits = gpt.apply(params, cfg, ids)
        t = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(t)
        seq.append(t)
    assert got == want


def test_admit_mid_decode_bit_identical(engine):
    """Continuous batching is transparent: sequence A's tokens are
    bit-identical whether it decodes alone or a second request is
    admitted into the running batch mid-generation (row-isolated
    math + same slot-config executable)."""
    solo = engine.submit([1, 2, 3, 4],
                         max_new_tokens=12).result(timeout_s=120)
    hA = engine.submit([1, 2, 3, 4], max_new_tokens=12)
    time.sleep(0.02)  # let A's decode get going before B arrives
    hB = engine.submit([9, 9], max_new_tokens=6)
    assert hA.result(timeout_s=120) == solo
    assert len(hB.result(timeout_s=120)) == 6


def test_retirement_frees_blocks(engine):
    """Blocks scale with live tokens: they are held while a sequence
    decodes and ALL return to the pool at retirement."""
    total = engine.kv_cfg.usable_blocks
    h = engine.submit([1, 2, 3], max_new_tokens=30)
    deadline = time.monotonic() + 60
    seen_used = 0
    while time.monotonic() < deadline:
        st = engine.status()
        seen_used = max(seen_used, st["kv"]["blocks_used"])
        if st["kv"]["blocks_used"] and st["active"]:
            break
        time.sleep(0.002)
    h.result(timeout_s=120)
    assert seen_used > 0, "allocation never observed while decoding"
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if engine.status()["kv"]["blocks_free"] == total:
            break
        time.sleep(0.01)
    st = engine.status()
    assert st["kv"]["blocks_free"] == total
    assert st["kv"]["blocks_used"] == 0


def test_finish_reasons(model):
    """max_new_tokens exhaustion reports "length"; sampling the
    configured eos id reports "eos" and stops immediately (the beam
    op's finished-freeze keeps the slot inert afterwards)."""
    probe = make_engine(model)
    probe.warmup()
    toks = probe.submit([1, 2, 3], max_new_tokens=3).result(timeout_s=120)
    h = probe.submit([1, 2, 3], max_new_tokens=3)
    assert h.result(timeout_s=120) == toks
    assert h.info["finish_reason"] == "length"
    probe.stop()
    eos_eng = make_engine(model, eos_id=toks[0])
    eos_eng.warmup()
    h = eos_eng.submit([1, 2, 3], max_new_tokens=10)
    assert h.result(timeout_s=120) == [toks[0]]
    assert h.info["finish_reason"] == "eos"
    eos_eng.stop()


def test_submit_validation(engine):
    with pytest.raises(ValueError):
        engine.submit([], max_new_tokens=4)
    with pytest.raises(ValueError):
        engine.submit([1] * 9, max_new_tokens=4)     # > largest bucket
    with pytest.raises(ValueError):
        engine.submit([999999], max_new_tokens=4)    # out of vocab
    with pytest.raises(ValueError):
        engine.submit([1, 2], max_new_tokens=0)


# ---------------------------------------------------------------------------
# Admission control / preemption
# ---------------------------------------------------------------------------


def _wait_active(eng, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.status()["active"]:
            return
        time.sleep(0.002)
    raise AssertionError("engine never admitted the request")


def test_queue_full_rejects(model):
    """Reject-not-block admission: with one long generation holding the
    only slot, the bounded waiting queue fills and the next submit
    raises QueueFullError."""
    eng = make_engine(model, decode_slots=(1,), max_queue=1, max_len=64)
    eng.warmup()
    a = eng.submit([1, 2, 3], max_new_tokens=50)     # long generation
    _wait_active(eng)                                # A holds the slot
    eng.submit([4, 5], max_new_tokens=2)             # waits (one slot)
    with pytest.raises(QueueFullError):
        eng.submit([6, 7], max_new_tokens=2)
    assert a.result(timeout_s=120)
    assert eng.status()["requests"]["rejected"] == 1
    eng.stop()


def test_preemption_recompute_is_transparent(model):
    """When the pool runs dry mid-decode, the youngest sequence is
    preempted (blocks freed, re-queued with prompt+generated) and
    re-prefilled later — emitted tokens are exactly the no-pressure
    run's, with no duplicates and no gaps."""
    # prefill buckets reach max_len so the preempt replay (original
    # prompt + generated tokens) always has a bucket to land in
    kw = dict(block_size=4, num_blocks=12, decode_slots=(2,),
              prefill_buckets=(8, 40), max_len=40)
    eng = make_engine(model, **kw)
    eng.warmup()
    # reference: each sequence alone (no pool pressure)
    ref_a = eng.submit([1, 2, 3, 4], max_new_tokens=24).result(
        timeout_s=120)
    ref_b = eng.submit([5, 6, 7], max_new_tokens=24).result(timeout_s=120)
    # concurrent: 2 growing sequences need 2*ceil(28/4)=14 > 11 blocks
    hA = eng.submit([1, 2, 3, 4], max_new_tokens=24)
    hB = eng.submit([5, 6, 7], max_new_tokens=24)
    got_a = hA.result(timeout_s=180)
    got_b = hB.result(timeout_s=180)
    assert got_a == ref_a
    assert got_b == ref_b
    assert eng.status()["requests"].get("preempted", 0) > 0
    eng.stop()


# ---------------------------------------------------------------------------
# Boot validation (PR 8 shape)
# ---------------------------------------------------------------------------


def test_boot_validation_findings_and_refusal(model, monkeypatch):
    from paddle_tpu.analysis import AnalysisError

    params, cfg = model
    # level unset: errors are recorded, boot proceeds (serving Engine
    # parity — only level 2 refuses)
    monkeypatch.delenv("PADDLE_TPU_VALIDATE", raising=False)
    eng = DecodeEngine(params, cfg, DecodeConfig(
        block_size=8, num_blocks=4, decode_slots=(2,),
        prefill_buckets=(8,), precision="f32", max_len=64))
    assert eng.analysis["errors"] >= 1  # pool can't hold one sequence
    monkeypatch.setenv("PADDLE_TPU_VALIDATE", "2")
    with pytest.raises(AnalysisError):
        DecodeEngine(params, cfg, DecodeConfig(
            block_size=8, num_blocks=4, decode_slots=(2,),
            prefill_buckets=(8,), precision="f32", max_len=64))
    with pytest.raises(AnalysisError, match="eos_id"):
        DecodeEngine(params, cfg, DecodeConfig(
            block_size=8, num_blocks=64, decode_slots=(2,),
            prefill_buckets=(8,), precision="f32", max_len=64,
            eos_id=10 ** 6))
    # MoE configs are refused: no expert-dispatch decode path
    moe_cfg = gpt.GPTConfig.tiny(n_experts=2)
    moe_params, _ = gpt.init(jax.random.key(0), moe_cfg)
    with pytest.raises(AnalysisError, match="MoE"):
        DecodeEngine(moe_params, moe_cfg, DecodeConfig(
            block_size=8, num_blocks=64, decode_slots=(2,),
            prefill_buckets=(8,), precision="f32", max_len=64))


def test_unknown_precision_fails_fast(model):
    params, cfg = model
    with pytest.raises(ValueError):
        DecodeEngine(params, cfg, DecodeConfig(precision="mixed_f16"))
    with pytest.raises(ValueError):
        DecodeEngine(params, cfg, DecodeConfig(precision="int7"))


def test_bf16_default_policy(model):
    """bf16 is the decode default (PR 7): pools and params ride the
    compute dtype, and generation works end to end."""
    params, cfg = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        block_size=8, num_blocks=32, decode_slots=(2,),
        prefill_buckets=(8,), max_len=48))
    assert eng.config.precision == "bf16"
    assert str(eng._pools[0].dtype) == "bfloat16"
    eng.warmup()
    toks = eng.submit([1, 2, 3], max_new_tokens=4).result(timeout_s=120)
    assert len(toks) == 4
    assert eng.status()["precision"] == "bf16"
    eng.stop()


# ---------------------------------------------------------------------------
# Warmstart phase grid
# ---------------------------------------------------------------------------


def test_warmstart_roundtrip_zero_compile(model, tmp_path, monkeypatch):
    """The PR 6 coldstart contract for the phase grid: a warm-booted
    engine adopts every phase executable, pays ZERO fresh compile
    events, and generates bit-identically to the cold engine. The
    compiles counted are the WARM ENGINE'S OWN (every compile record
    carries its dispatcher's `meta` dict): the process-wide
    `paddle_tpu_compile_seconds` also moves when another engine of the
    same test worker compiles meanwhile."""
    from paddle_tpu.core import executor

    kw = dict(decode_slots=(2, 4), prefill_buckets=(8, 16))
    cold = make_engine(model, **kw)
    ready = cold.warmup()
    # 2 buckets + 2 slot configs + the id assembly of each pair of them
    assert ready == 8
    art = str(tmp_path / "decode.warmstart")
    assert cold.export_warmstart(art) == 8
    prompt = [3, 1, 4, 1, 5]
    cold_toks = cold.submit(prompt, max_new_tokens=6).result(
        timeout_s=120)
    cold.stop()

    compiled = []       # (kind, the dispatcher's meta) of every compile
    real = executor._telemetry.record_compile
    monkeypatch.setattr(
        executor._telemetry, "record_compile",
        lambda kind, seconds, **kw: (compiled.append((kind, kw.get("meta"))),
                                     real(kind, seconds, **kw))[1])
    warm = make_engine(model, warmstart=art, **kw)
    assert warm.warmstart_adopted == 8
    assert warm.warmup() == 8
    warm_toks = warm.submit(prompt, max_new_tokens=6).result(
        timeout_s=120)
    warm.stop()
    grid = [warm._phase_dispatch(key) for key in warm._phase_keys()]
    assert len(grid) == 8
    fresh = [kind for kind, meta in compiled
             if any(meta is d._meta for d in grid)]
    assert fresh == [], fresh
    # nor did a phase fall back to the plain jit path, which compiles too
    assert [d._recorded_jit_compiles for d in grid] == [0] * 8
    assert warm_toks == cold_toks


def test_warmstart_digest_reject(model, tmp_path):
    """An artifact baked from different params (or grid) is rejected
    whole with a warmstart reject event — cold boot, never wrong
    tokens."""
    cold = make_engine(model)
    cold.warmup()
    art = str(tmp_path / "decode.warmstart")
    cold.export_warmstart(art)
    cold.stop()
    params2, _ = gpt.init(jax.random.key(1), gpt.GPTConfig.tiny())
    cfg2 = gpt.GPTConfig.tiny()
    cfg2.dtype = "float32"
    seq0 = events.recent()[-1]["seq"] if events.recent() else -1
    other = DecodeEngine(params2, cfg2, DecodeConfig(
        block_size=8, num_blocks=64, decode_slots=(4,),
        prefill_buckets=(8,), precision="f32", max_len=64,
        warmstart=art))
    assert other.warmstart_adopted == 0
    rejects = [e for e in events.recent(kind="warmstart")
               if e["seq"] > seq0 and e.get("action") == "reject"]
    assert rejects and "digest" in rejects[0]["reason"]
    # garbage artifact: same degradation, no crash
    bad = str(tmp_path / "garbage")
    with open(bad, "wb") as f:  # atomic-exempt: test fixture artifact
        f.write(b"not a pickle")
    assert other.load_warmstart(bad) == 0
    other.stop()


# ---------------------------------------------------------------------------
# HTTP streaming frontend
# ---------------------------------------------------------------------------


def test_streaming_http_e2e(model):
    eng = make_engine(model, max_queue=8)
    eng.warmup()
    srv = Server(ServingConfig(warmup=False), decode=eng)
    port = srv.start(0)
    url = f"http://127.0.0.1:{port}/v1/generate"

    def post(payload, timeout=60):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=timeout)

    try:
        # chunked stream: tokens arrive as ndjson lines, closed by a
        # done record carrying finish_reason + ttft
        with post({"ids": [1, 2, 3], "max_new_tokens": 5}) as r:
            assert r.headers.get("Transfer-Encoding") == "chunked"
            recs = [json.loads(ln) for ln in r if ln.strip()]
        toks = [rec["token"] for rec in recs if "token" in rec]
        done = recs[-1]
        assert len(toks) == 5
        assert done["done"] and done["tokens"] == 5
        assert done["finish_reason"] == "length"
        assert done["ttft_ms"] > 0
        # non-stream reply carries the same tokens (deterministic)
        with post({"ids": [1, 2, 3], "max_new_tokens": 5,
                   "stream": False}) as r:
            body = json.loads(r.read())
        assert body["tokens"] == toks
        # status carries the decode block
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/status", timeout=30) as r:
            st = json.loads(r.read())
        assert st["decode"]["phase_grid"]["decode_slots"] == [4]
        assert st["decode"]["requests"]["length"] >= 2
        # malformed requests are 400s
        for bad in ({"max_new_tokens": 4}, {"ids": []},
                    {"ids": [10 ** 9]}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(bad)
            assert ei.value.code == 400
        # /v1/predict on a decode-only server: 503, not a crash
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/predict",
            data=json.dumps({"feeds": {"x": [[1.0]]}}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
    finally:
        srv.stop()


def test_http_queue_full_503(model):
    eng = make_engine(model, decode_slots=(1,), max_queue=1, max_len=64)
    eng.warmup()
    srv = Server(ServingConfig(warmup=False), decode=eng)
    port = srv.start(0)
    url = f"http://127.0.0.1:{port}/v1/generate"
    try:
        # long active generation + one waiting fills the queue
        eng.submit([1, 2, 3], max_new_tokens=50)
        _wait_active(eng)
        eng.submit([4, 5], max_new_tokens=2)
        req = urllib.request.Request(
            url, data=json.dumps({"ids": [6, 7],
                                  "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
    finally:
        srv.stop()


def test_block_boundary_admit_after_retire(model):
    """Regression: a request admitted on the retire path (the mid-loop
    _admit after a finished sequence frees its slot) whose prompt
    length is an EXACT block multiple must get its next block before
    the dispatch — without the _grow_blocks call there, its first
    decode token's K/V landed in the null block and its attention was
    silently corrupted from that step on."""
    kw = dict(decode_slots=(1,), prefill_buckets=(8,), block_size=8,
              num_blocks=32, max_len=64)
    eng = make_engine(model, **kw)
    eng.warmup()
    prompt_b = [7, 1, 3, 5, 2, 6, 4, 1]        # len == block_size
    solo = eng.submit(prompt_b, max_new_tokens=10).result(timeout_s=120)
    # occupy the single slot, queue B behind it: B is admitted by the
    # mid-loop _admit the moment A retires
    hA = eng.submit([1, 2, 3], max_new_tokens=20)
    _wait_active(eng)
    hB = eng.submit(prompt_b, max_new_tokens=10)
    assert len(hA.result(timeout_s=120)) == 20
    assert hB.result(timeout_s=120) == solo
    eng.stop()


# ---------------------------------------------------------------------------
# The lazy loop's queue on the device (ISSUE 32): an admission's first
# token stays there, a changed batch gets its ids there
# ---------------------------------------------------------------------------


_FORWARD = {}


def _reference(model, prompt, max_new, eos_id=None):
    """Greedy tokens of one sequence alone through the full-context
    forward pass (no engine, no cache): what every stream must equal."""
    params, cfg = model
    if "fn" not in _FORWARD:
        _FORWARD["fn"] = jax.jit(lambda p, ids: gpt.apply(p, cfg, ids))
    seq, out = list(prompt), []
    for _ in range(max_new):
        ids = np.zeros((1, 64), np.int32)       # causal: the tail is inert
        ids[0, :len(seq)] = seq
        logits = np.asarray(_FORWARD["fn"](params, ids))
        out.append(int(np.argmax(logits[0, len(seq) - 1])))
        seq.append(out[-1])
        if out[-1] == eos_id:
            break
    return out


def _submit_together(eng, *jobs):
    """Every job waits before the scheduler's first turn sees any: the
    loop takes `_cv` (re-entrant for its owner) to look at the queue."""
    with eng._cv:
        return [eng.submit(p, max_new_tokens=n) for p, n in jobs]


def _case_admissions_while_residents_decode(model):
    eng = make_engine(model, prefill_buckets=(8,))
    jobs = [([1, 2, 3, 4], 30), ([9, 9], 12), ([5, 6, 7], 9), ([3], 14)]
    hs = [eng.submit(*jobs[0])]
    for job in jobs[1:]:
        _wait_active(eng)
        time.sleep(0.01)            # the residents are some steps on
        hs.append(eng.submit(*job))
    return eng, hs, jobs, {}


def _case_retirement_and_admission_in_one_turn(model):
    # two slots, three requests: C waits for a slot, so the turn that sees
    # A gone is the turn that admits C, beside B still decoding
    eng = make_engine(model, decode_slots=(2,))
    jobs = [([1, 2, 3], 5), ([4, 5, 6, 7], 24), ([8, 9], 10)]
    return eng, _submit_together(eng, *jobs), jobs, {}


def _case_block_multiple_prompt_after_a_retirement(model):
    eng = make_engine(model, decode_slots=(1,), num_blocks=32)
    jobs = [([1, 2, 3], 12), ([7, 1, 3, 5, 2, 6, 4, 1], 10)]   # len == BS
    return eng, _submit_together(eng, *jobs), jobs, {}


def _case_preemption_with_a_first_token_in_flight(model):
    # three blocks of four: A and B are admitted in one turn (a block
    # each), A's growth takes the third, B's finds none: the loop drains
    # with both first tokens still on the device, then preempts B, whose
    # replay prompt must hold the first token it was given
    eng = make_engine(model, block_size=4, num_blocks=4, decode_slots=(2,),
                      prefill_buckets=(4, 12), max_len=12)
    jobs = [([1, 2, 3, 4], 8), ([5, 6, 7, 8], 6)]
    return eng, _submit_together(eng, *jobs), jobs, \
        {"preempted": True, "drains": True}


def _case_cancel_with_its_prefill_in_flight(model):
    from paddle_tpu.serving.decode import DecodeHandle

    eng = make_engine(model)
    jobs = [([1, 2, 3, 4], 20), ([6, 5], 20), ([2, 2, 2], 8)]
    real = eng._prefill_one

    def prefill_then_cancel(req):
        first = real(req)
        if list(req.prompt) == jobs[1][0]:
            eng.cancel(DecodeHandle(req))    # its first token is in flight
        return first

    eng._prefill_one = prefill_then_cancel
    hs = [eng.submit(*jobs[0])]
    _wait_active(eng)
    hs += [eng.submit(*job) for job in jobs[1:]]
    return eng, hs, jobs, {"cancelled": 1}


def _case_eos_first_and_one_token_requests(model):
    eos = _reference(model, [1, 2, 3], 1)[0]
    eng = make_engine(model, eos_id=eos)
    jobs = [([4, 5, 6, 7], 20), ([1, 2, 3], 10), ([7, 7], 1), ([2, 8], 1)]
    hs = [eng.submit(*jobs[0])]
    _wait_active(eng)
    hs += [eng.submit(*job) for job in jobs[1:]]
    return eng, hs, jobs, {"eos_id": eos, "drains": True,
                           "reasons": {1: "eos", 2: "length", 3: "length"}}


@pytest.mark.parametrize("case", [
    _case_admissions_while_residents_decode,
    _case_retirement_and_admission_in_one_turn,
    _case_block_multiple_prompt_after_a_retirement,
    _case_preemption_with_a_first_token_in_flight,
    _case_cancel_with_its_prefill_in_flight,
    _case_eos_first_and_one_token_requests,
], ids=lambda f: f.__name__[len("_case_"):])
def test_lazy_loop_streams_equal_the_solo_reference(model, case):
    """No token comes to the host before the next dispatch, and every
    stream is still the one its sequence gives alone: none missing, none
    twice, none another request's."""
    eng, handles, jobs, expect = case(model)
    try:
        streams = [h.result(timeout_s=180) for h in handles]
        status = eng.status()
        deadline = time.monotonic() + 30
        while status["kv"]["blocks_used"] and time.monotonic() < deadline:
            time.sleep(0.01)
            status = eng.status()
    finally:
        eng.stop()
    cancelled = expect.get("cancelled")
    for i, ((prompt, n), got, h) in enumerate(zip(jobs, streams, handles)):
        want = _reference(model, prompt, n, expect.get("eos_id"))
        if i == cancelled:
            assert h.info["finish_reason"] == "cancelled"
            assert len(got) < n and got == want[:len(got)], (got, want)
        else:
            assert got == want, (i, got, want)
            assert h.info["finish_reason"] == expect.get(
                "reasons", {}).get(i, h.info["finish_reason"])
    assert status["kv"]["blocks_used"] == 0 and status["active"] == 0
    assert bool(status["requests"]["preempted"]) \
        == bool(expect.get("preempted"))
    pipe = status["pipeline"]
    assert bool(pipe["drains"]) == bool(expect.get("drains")), pipe
    assert pipe["assembled"] >= 1


_BACKEND_COMPILES = []


def _backend_compiles() -> int:
    """Programs this process has compiled since the first call (the
    listener stays registered: jax takes none off)."""
    if not _BACKEND_COMPILES:
        _BACKEND_COMPILES.append(0)

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _BACKEND_COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _BACKEND_COMPILES[0]


def _wait_admitted(eng, n, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.status()["active"] >= n:
            return
        time.sleep(0.001)
    raise AssertionError(f"engine never held {n} sequences")


def test_admissions_are_assembled_on_the_device_and_warmed(model, tmp_path):
    """The mechanism engages: each of K admissions into a decoding batch
    is one `decode.dispatch` whose ids were put together on the device,
    nothing is built from host tokens while something is in flight, the
    queue is never drained, and the assembly programs are part of the
    warmed (and warm-started) grid: no program compiles under load."""
    from paddle_tpu.observability import tracing

    kw = dict(decode_slots=(2, 4), prefill_buckets=(8,))
    cold = make_engine(model, **kw)
    n_grid = cold.warmup()
    art = str(tmp_path / "decode.warmstart")
    assert cold.export_warmstart(art) == n_grid == 1 + 2 + 4
    K = 3
    jobs = [([1, 2, 3, 4], 50), ([9, 9], 50), ([5, 6, 7], 50), ([3], 50)]
    runs = {}
    for boot in ("warmup", "warmstart"):
        eng = cold if boot == "warmup" else make_engine(
            model, warmstart=art, **kw)
        try:
            if boot == "warmstart":
                assert eng.warmstart_adopted == n_grid
                assert eng.warmup() == n_grid
            compiled = _backend_compiles()
            tracing.clear_spans()
            with tracing.recorded():
                hs = []
                for i, job in enumerate(jobs):
                    hs.append(eng.submit(job[0], max_new_tokens=job[1]))
                    _wait_admitted(eng, i + 1)
                runs[boot] = [h.result(timeout_s=180) for h in hs]
                pipe = eng.status()["pipeline"]
            assert _backend_compiles() == compiled
        finally:
            eng.stop()
        grid = [eng._phase_dispatch(key) for key in eng._phase_keys()]
        assert [d._recorded_jit_compiles for d in grid] == [0] * n_grid
        disp = sorted((s for s in tracing.get_spans()
                       if s.name == "decode.dispatch"), key=lambda s: s.ts)
        # dispatches and admissions before which the queue was seen empty
        starved = sum(s.args["queue_empty"] for s in tracing.get_spans()
                      if s.name in ("decode.dispatch", "decode.prefill"))
        tracing.clear_spans()
        how = [s.args["ids"] for s in disp]
        joined = [s for prev, s in zip(disp, disp[1:])
                  if s.args["live"] > prev.args["live"]]
        assert len(joined) == K
        assert {s.args["ids"] for s in joined} == {"assembled"}
        # the first step's ids are its prefill's token, on the device too
        assert how[0] == "assembled" and "host" not in how
        assert pipe == {"fed": how.count("fed"),
                        "assembled": how.count("assembled"),
                        "host": 0, "drains": 0, "starved": starved}
        assert pipe["fed"] > pipe["assembled"] >= K + 1
    assert runs["warmup"] == runs["warmstart"] \
        == [_reference(model, p, n) for p, n in jobs]


def test_stop_drains_preenqueued_requests(model):
    """A request enqueued while no scheduler thread exists is drained
    by stop() itself (the _loop finally never runs for a thread never
    started) — its stream terminates with finish_reason='cancelled'
    instead of blocking its caller forever."""
    eng = make_engine(model)
    with eng._cv:                     # enqueue without starting
        eng._rid += 1
        from paddle_tpu.serving.decode import _Request
        req = _Request(eng._rid, np.array([1, 2], np.int32), 4)
        eng._waiting.append(req)
    eng.stop()
    from paddle_tpu.serving.decode import DecodeHandle
    assert DecodeHandle(req).result(timeout_s=10) == []
    assert req.finish_reason == "cancelled"


def test_the_first_token_is_timed_where_it_leaves_the_outbox(model):
    """The time to the first token ends where the token is handed to its
    reader, the outbox's hold inside it: `t_first` (and with it
    `decode.ttft` and `http.first_write`'s start) is stamped by the flush
    that delivers the token, not where the turn resolved it; a request
    whose only token leaves with its end has one too."""
    eng = make_engine(model)
    flushes = []
    flush = eng._flush_outbox

    def timed():
        flushes.append((time.monotonic(), [
            req for req, item in eng._outbox
            if item is not None and req.t_first is None]))
        flush()

    eng._flush_outbox = timed
    try:
        handles = [eng.submit([1, 2, 3 + i], max_new_tokens=n)
                   for i, n in enumerate((1, 4, 2))]
        assert [len(h.result(timeout_s=120)) for h in handles] == [1, 4, 2]
    finally:
        eng.stop()
    firsts = [(at, req) for at, reqs in flushes for req in reqs]
    assert {req.rid for _, req in firsts} >= {
        h._req.rid for h in handles[1:]}
    for at, req in firsts:
        assert req.t_first >= at > req.t_submit
    assert all(h.t_first is not None for h in handles)


def test_tokens_reach_their_readers_after_the_next_dispatch(model):
    """What a turn resolves waits in the loop's outbox until the next
    step is dispatched (or nothing is left to dispatch), in order, the
    stream's end after its last token; outside the loop's thread there is
    no outbox."""
    eng = make_engine(model)
    assert eng._outbox is None
    try:
        handles = [eng.submit([1, 2, 3 + i], max_new_tokens=n)
                   for i, n in enumerate((1, 5, 2))]
        streams = [h.result(timeout_s=120) for h in handles]
        assert [len(s) for s in streams] == [1, 5, 2]
        assert eng._outbox == []            # all of it handed over
        solo = eng.submit([1, 2, 4], max_new_tokens=5).result(timeout_s=120)
        assert solo == streams[1]
    finally:
        eng.stop()
    assert eng._outbox is None
    # a request finished by `stop()` before any loop ran ends at once
    from paddle_tpu.serving.decode import DecodeHandle, _Request
    late = make_engine(model)
    req = _Request(1, np.array([1, 2], np.int32), 4)
    late._finish(req, "cancelled")
    assert DecodeHandle(req).result(timeout_s=5) == []
    late.stop()


def test_client_disconnect_cancels_generation(model):
    """A streaming client that hangs up mid-generation must not keep
    its slot/KV blocks for the full max_new_tokens: the frontend
    cancels the handle and the scheduler retires it, freeing the
    pool."""
    import http.client

    eng = make_engine(model, max_len=64)
    eng.warmup()
    srv = Server(ServingConfig(warmup=False), decode=eng)
    port = srv.start(0)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/generate",
                     body=json.dumps({"ids": [1, 2, 3],
                                      "max_new_tokens": 55}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.readline()           # first token arrived → mid-stream
        conn.close()              # hang up
        deadline = time.monotonic() + 30
        st = eng.status()
        while time.monotonic() < deadline:
            st = eng.status()
            if st["requests"].get("cancelled", 0) >= 1 \
                    and st["kv"]["blocks_used"] == 0 \
                    and st["active"] == 0:
                break
            time.sleep(0.01)
        assert st["requests"].get("cancelled", 0) >= 1, st
        assert st["kv"]["blocks_used"] == 0
    finally:
        srv.stop()


def test_engine_cancel_api(model, engine):
    """DecodeEngine.cancel retires a live generation early; the
    abandoned stream ends (finish_reason='cancelled') instead of
    running to max_new_tokens."""
    h = engine.submit([2, 3, 4], max_new_tokens=58)
    _wait_active(engine)
    engine.cancel(h)
    toks = h.result(timeout_s=60)
    assert len(toks) < 58
    assert h.info["finish_reason"] == "cancelled"


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------


def test_decode_metrics_and_obsdump(model, engine, tmp_path, capsys):
    engine.submit([2, 4, 6], max_new_tokens=4).result(timeout_s=120)
    snap = observability.snapshot()
    assert snap["paddle_tpu_decode_tokens_total"]["series"]
    assert snap["paddle_tpu_decode_ttft_seconds"]["series"][0]["count"] \
        >= 1
    path = tmp_path / "metrics.json"
    path.write_text(json.dumps(snap))
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import obsdump
    finally:
        sys.path.pop(0)
    assert obsdump.main(["decode", str(path)]) == 0
    out = capsys.readouterr().out
    assert "tokens:" in out and "kv blocks:" in out and "ttft:" in out
    assert obsdump.main(["decode", str(path), "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["tokens"].get("decode", 0) >= 1
    assert rec["ttft"]["count"] >= 1


def test_slot_config_grid_warmed(model):
    eng = make_engine(model, decode_slots=(2, 4))
    # 1 bucket + 2 slot configs + the id assembly of each pair of them
    assert eng.warmup() == 7
    assert all(d._aot is not None for d in eng._decode.values())
    assert all(d._aot is not None for d in eng._prefill.values())
    hs = [eng.submit([i + 1, i + 2], max_new_tokens=3) for i in range(3)]
    assert all(len(h.result(timeout_s=120)) == 3 for h in hs)
    assert eng.status()["slot_config"] in (2, 4)
    eng.stop()
