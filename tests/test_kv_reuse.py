"""KV-reuse subsystem (ISSUE 18, SERVING.md §KV reuse): block-level
prefix caching, chunked prefill, and speculative decoding.

The load-bearing correctness claims pinned here:

- chunked prefill emits EXACTLY the whole-prompt bucketed engine's
  tokens for every prompt length (the chunk program's masked partial
  attention == the full prefill);
- prefix-cache adoption is transparent: a prompt served from cached
  blocks generates bit-identically to a cold prompt, and the chain
  hash only matches blocks whose ENTIRE prefix agrees;
- copy-on-write is a real safety net: a forced share diverges onto a
  private copy with the original block's contents untouched and the
  stream unchanged;
- eviction (LRU, oldest-first, folded into alloc) composes with
  recompute-preemption — pressure changes latency, never tokens, and
  every refcount drains to zero on retire/cancel (no double-free);
- speculative decoding with the exact greedy accept rule is
  bit-identical to plain decode, and a self-draft accepts everything;
- the re-keyed (chunk+spec) phase grid round-trips through warmstart
  with zero fresh compiles;
- retained cache blocks are their own memwatch owner, distinct from
  kv_pool.
"""

import time

import numpy as np
import pytest

import jax

import paddle_tpu  # noqa: F401 — package init registers telemetry
from paddle_tpu import observability
from paddle_tpu.models import gpt
from paddle_tpu.observability import memwatch
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_cache import KVCacheConfig, NoBlocksError
from paddle_tpu.serving.kv_reuse import (ReuseBlockAllocator,
                                         accept_length, hash_blocks)


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"  # exactness vs the bucketed reference
    params, _ = gpt.init(jax.random.key(0), cfg)
    return params, cfg


def make_engine(model, draft=None, **kw):
    params, cfg = model
    base = dict(block_size=8, num_blocks=64, decode_slots=(4,),
                precision="f32", max_len=64)
    base.update(kw)
    return DecodeEngine(params, cfg, DecodeConfig(**base), draft=draft)


def _prompts():
    """Shared 19-token prefix + distinct suffixes, plus odd lengths
    exercising sub-chunk, chunk-aligned, and block-boundary prompts."""
    rng = np.random.RandomState(7)
    vocab = gpt.GPTConfig.tiny().vocab_size
    shared = rng.randint(0, vocab, size=(19,)).tolist()
    return [shared + rng.randint(0, vocab, size=(n,)).tolist()
            for n in (5, 2, 13)] + [[3, 1, 4], list(range(1, 9))]


def _run(eng, prompts, n=10):
    hs = [eng.submit(p, max_new_tokens=n) for p in prompts]
    return [h.result(timeout_s=180) for h in hs]


def _compile_counts():
    snap = observability.snapshot()
    comp = snap.get("paddle_tpu_compile_seconds") or {"series": []}
    out = {}
    for s in comp["series"]:
        k = s["labels"].get("kind", "?")
        out[k] = out.get(k, 0) + s["count"]
    return out


@pytest.fixture(scope="module")
def reference(model):
    """Greedy streams from the plain bucketed engine — the baseline
    every reuse configuration must reproduce bit-identically."""
    eng = make_engine(model, prefill_buckets=(32,))
    eng.warmup()
    try:
        return _run(eng, _prompts())
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Pure units: chain hash + accept rule + allocator lifecycle
# ---------------------------------------------------------------------------


def test_hash_blocks_commits_to_whole_prefix():
    a = hash_blocks(list(range(24)), 8)
    assert len(a) == 3                     # trailing partials excluded
    assert len(hash_blocks(list(range(23)), 8)) == 2
    # identical prefixes agree block-for-block
    b = hash_blocks(list(range(24)) + [99], 8)
    assert a == b[:3]
    # same block CONTENTS under a different prefix must not collide —
    # the chain is what makes per-block sharing safe
    c = hash_blocks([7] * 8 + list(range(8, 16)), 8)
    assert c[1] != a[1]
    # block size participates in the seed: no cross-geometry matches
    assert hash_blocks(list(range(8)), 8)[0] != \
        hash_blocks(list(range(8)), 4)[0]


def test_accept_length_exact_greedy_rule():
    # out[j] = target output after accepting draft[:j]
    assert accept_length([5, 6, 7], [5, 6, 7, 8]) == 3   # full accept
    assert accept_length([5, 6, 7], [5, 6, 9, 8]) == 2   # reject at 2
    assert accept_length([5, 6, 7], [4, 6, 7, 8]) == 0   # reject first
    assert accept_length([], [4]) == 0                   # k=0 degenerate


def _acfg(num_blocks=8):
    return KVCacheConfig(layers=1, kv_heads=1, head_dim=2, max_len=32,
                         block_size=8, num_blocks=num_blocks)


def test_reuse_allocator_refcount_lifecycle():
    al = ReuseBlockAllocator(_acfg())
    h = hash_blocks(list(range(16)), 8)
    got = al.alloc(2)
    assert all(al.refcount(b) == 1 for b in got)
    al.register(got[0], h[0])
    al.register(got[1], h[1])
    # a second reader: match increments, free decrements
    hit = al.match_prefix(h)
    assert hit == got and al.refcount(got[0]) == 2
    assert al.is_shared(got[0])
    al.free(hit)
    assert al.refcount(got[0]) == 1 and not al.is_shared(got[0])
    # last ref: registered blocks PARK (still indexed), not freed
    free_before = al.free_blocks()
    al.free(got)
    assert al.cached_blocks() == 2
    assert al.used_blocks() == 0
    assert al.free_blocks() == free_before      # parked, not released
    # double free still a programming error
    with pytest.raises(ValueError):
        al.free(got[:1])
    # a hit on a parked block revives it with refcount 1
    rev = al.match_prefix(h[:1])
    assert rev == got[:1] and al.refcount(got[0]) == 1
    assert al.cached_blocks() == 1
    al.free(rev)
    st = al.stats(live_tokens=0)
    assert st["blocks_cached"] == 2
    assert st["prefix_hits_total"] == 3 and st["prefix_misses_total"] == 0
    assert st["blocks_reused_total"] == 3


def test_reuse_allocator_eviction_oldest_first():
    al = ReuseBlockAllocator(_acfg(num_blocks=6))   # 5 usable
    old = al.alloc(2)
    h_old = hash_blocks(list(range(16)), 8)
    for b, h in zip(old, h_old):
        al.register(b, h)
    al.free(old)                                    # parked (oldest)
    new = al.alloc(1)
    h_new = hash_blocks([9] * 8, 8)
    al.register(new[0], h_new[0])
    al.free(new)                                    # parked (newest)
    # free list holds 2; asking for 4 must evict exactly the 2 OLDEST
    assert al.can_alloc(5) and not al.can_alloc(6)
    got = al.alloc(4)
    assert al.evicted_total == 2
    assert al.match_prefix(h_old) == []             # old entries gone
    assert al.match_prefix(h_new) == [new[0]]       # newest survived
    assert al.refcount(new[0]) == 1
    al.free(got + [new[0]])
    # exhaustion still refuses with nothing granted
    al2 = ReuseBlockAllocator(_acfg(num_blocks=6))
    al2.alloc(3)
    with pytest.raises(NoBlocksError):
        al2.alloc(3)
    assert al2.free_blocks() == 2


def test_reuse_allocator_register_and_cow_contracts():
    al = ReuseBlockAllocator(_acfg())
    h = hash_blocks(list(range(8)), 8)
    a = al.alloc(1)[0]
    b = al.alloc(1)[0]
    al.register(a, h[0])
    # first registration wins: b keeps serving privately, a keeps hits
    al.register(b, h[0])
    assert al.match_prefix(h) == [a]
    al.free([a])
    # registering a dead block is a programming error
    al.free([b])
    with pytest.raises(ValueError):
        al.register(b, hash_blocks([5] * 8, 8)[0])
    # COW only applies to genuinely shared blocks
    c = al.alloc(1)[0]
    with pytest.raises(ValueError):
        al.cow_alloc(c)
    al.incref(c)
    priv = al.cow_alloc(c)
    assert priv != c and al.refcount(c) == 1 and al.refcount(priv) == 1
    assert al.cow_total == 1


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_whole_prefill(model, reference):
    """Fixed-size chunk slices (with a partial, masked final slice)
    must reproduce the bucketed whole-prompt prefill exactly — same
    first token, same stream — for prompts below, at, and above the
    chunk size."""
    eng = make_engine(model, prefill_chunk=8)
    eng.warmup()
    try:
        assert _run(eng, _prompts()) == reference
    finally:
        eng.stop()


def test_chunked_path_retires_bucket_coverage_warning(model):
    """Bucketed engines warn when the largest prefill bucket < max_len
    (a preemption replay can outgrow the bucket set); the chunk
    program covers ANY length under max_len, so the warning is retired
    there — and prompts beyond the old bucket ceiling are accepted."""
    bucketed = make_engine(model, prefill_buckets=(8,))
    assert bucketed.analysis["warnings"] >= 1
    with pytest.raises(ValueError):
        bucketed.submit([1] * 9, max_new_tokens=2)   # > largest bucket
    bucketed.stop()
    chunked = make_engine(model, prefill_chunk=8)
    assert chunked.analysis["warnings"] == 0
    assert chunked.analysis["errors"] == 0
    chunked.warmup()
    try:
        got = chunked.submit(list(range(1, 40)),
                             max_new_tokens=3).result(timeout_s=120)
        assert len(got) == 3
        with pytest.raises(ValueError):
            chunked.submit([1] * 64, max_new_tokens=2)  # >= max_len
    finally:
        chunked.stop()


# ---------------------------------------------------------------------------
# Prefix caching
# ---------------------------------------------------------------------------


def test_prefix_cache_bit_identical_with_hits(model, reference):
    """Shared-prefix prompts resolve their common full blocks from the
    cache (second wave prefills only the novel suffix) and the streams
    stay bit-identical to the no-cache baseline both cold and warm."""
    eng = make_engine(model, prefill_chunk=8, prefix_cache=True)
    eng.warmup()
    try:
        cold = _run(eng, _prompts())
        assert cold == reference
        warm = _run(eng, _prompts())
        assert warm == reference
        st = eng.status()
        kv = st["kv"]
        assert kv["prefix_hits_total"] > 0
        assert kv["blocks_reused_total"] > 0
        assert kv["blocks_cached"] > 0          # parked for future hits
        assert kv["blocks_used"] == 0           # every refcount drained
        assert st["kv_reuse"]["prefix_cache"] is True
        snap = observability.snapshot()
        events = {s["labels"]["event"]: s["value"] for s in
                  snap["paddle_tpu_prefix_cache_total"]["series"]}
        assert events.get("hit", 0) >= kv["prefix_hits_total"]
        assert snap["paddle_tpu_decode_blocks_reused"]["series"][0][
            "value"] > 0
    finally:
        eng.stop()


def test_prefix_cache_memwatch_owner(model):
    """Retained cache blocks are owner-tagged HBM: the memwatch sweep
    reports them as a `prefix_cache` row (bytes live INSIDE the
    kv_pool arrays, so the row rides alongside the total — the OOM
    forensics / /v1/status memory view, not double-counted)."""
    eng = make_engine(model, prefill_chunk=8, prefix_cache=True)
    eng.warmup()
    try:
        _run(eng, _prompts()[:1], n=4)
        cached = eng.status()["kv"]["blocks_cached"]
        assert cached > 0
        rep = memwatch.sweep(force=True)
        assert rep["owners"].get("prefix_cache") == \
            cached * eng._prefix_block_bytes()
        assert rep["owners"].get("kv_pool", 0) > 0   # distinct owners
    finally:
        eng.stop()


def test_cow_forced_share_diverges_onto_private_copy(model, reference):
    """COW safety net via a forced share: an extra reference is taken
    on the block the first decode write will land in (normal admission
    never shares a write-span block). The write must trigger
    copy-on-write — stream unchanged, the ORIGINAL block's contents
    bit-identical after generation, and the forced reference still
    accounted (no double-free when the sequence retires)."""
    eng = make_engine(model, prefill_chunk=8, prefix_cache=True)
    eng.warmup()
    # len 21: the first decode write (position 21) lands inside the
    # LAST prompt block (index 2, holding tokens 16..20) — the one
    # block a forced share can make COW fire on
    prompt = _prompts()[1]
    state = {}
    orig_pump = eng._pump_chunk

    def pump_then_share():
        orig_pump()
        # scheduler thread: safe to inspect _active without racing
        for r in eng._active:
            if not state and r.pos == len(r.prompt):
                bi = r.pos // eng.kv_cfg.block_size
                blk = r.blocks[bi]
                eng._alloc.incref(blk)
                kp, vp = eng._pools
                state["snap"] = (blk, np.asarray(kp[:, blk]).copy(),
                                 np.asarray(vp[:, blk]).copy())

    eng._pump_chunk = pump_then_share
    try:
        got = eng.submit(prompt, max_new_tokens=10).result(timeout_s=180)
        assert got == reference[1]
        blk, k0, v0 = state["snap"]
        assert eng._alloc.cow_total >= 1
        assert eng.status()["kv"]["cow_total"] >= 1
        # the shared block was never written: its KV is byte-for-byte
        # what it held when the share was forced
        kp, vp = eng._pools
        np.testing.assert_array_equal(np.asarray(kp[:, blk]), k0)
        np.testing.assert_array_equal(np.asarray(vp[:, blk]), v0)
        # retirement dropped the engine's references; ours is the last
        assert eng._alloc.refcount(blk) == 1
        eng._alloc.free([blk])
        assert eng._alloc.refcount(blk) == 0
    finally:
        eng._pump_chunk = orig_pump
        eng.stop()


def test_eviction_composes_with_preemption(model):
    """Pool pressure with a populated cache: LRU eviction reclaims the
    parked blocks first, then recompute-preemption kicks in — emitted
    tokens are exactly the no-pressure run's, refcounts all drain, and
    a cancelled in-flight request releases its reservation too."""
    kw = dict(block_size=4, num_blocks=12, decode_slots=(2,),
              prefill_chunk=4, prefix_cache=True, max_len=40)
    eng = make_engine(model, **kw)
    eng.warmup()
    try:
        # populate the cache: 9-token prompt registers 2 full blocks
        seed = list(range(10, 19))
        eng.submit(seed, max_new_tokens=2).result(timeout_s=120)
        assert eng.status()["kv"]["blocks_cached"] >= 2
        # no-pressure references (sequential; pool never short)
        ref_a = eng.submit([1, 2, 3, 4], max_new_tokens=24).result(
            timeout_s=180)
        ref_b = eng.submit([5, 6, 7], max_new_tokens=24).result(
            timeout_s=180)
        # concurrent: 2 sequences growing to 28 tokens need 14 blocks
        # of 11 usable — evicts every parked block, then preempts
        hA = eng.submit([1, 2, 3, 4], max_new_tokens=24)
        hB = eng.submit([5, 6, 7], max_new_tokens=24)
        assert hA.result(timeout_s=180) == ref_a
        assert hB.result(timeout_s=180) == ref_b
        st = eng.status()
        assert st["kv"]["evictions_total"] >= 2
        assert st["requests"].get("preempted", 0) >= 1
        assert st["kv"]["blocks_used"] == 0          # refcounts drained
        # cancel mid-flight: the reservation drains the same way
        h = eng.submit(list(range(20, 39)), max_new_tokens=15)
        time.sleep(0.05)
        eng.cancel(h)
        h.result(timeout_s=120)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = eng.status()
            if st["kv"]["blocks_used"] == 0 and st["active"] == 0:
                break
            time.sleep(0.01)
        assert st["kv"]["blocks_used"] == 0
        assert st["kv"]["blocks_cached"] + st["kv"]["blocks_free"] == \
            eng.kv_cfg.usable_blocks
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Speculative decoding
# ---------------------------------------------------------------------------


def test_spec_decode_bit_identical_self_draft(model, reference):
    """Self-draft (draft == target): every proposal verifies, accept
    rate is exactly 1.0, and the stream is bit-identical to plain
    greedy decode — including near-max_len rounds that demote to the
    plain path."""
    params, cfg = model
    eng = make_engine(model, prefill_chunk=8, prefix_cache=True,
                      spec_k=2, draft=(params, cfg))
    eng.warmup()
    try:
        assert _run(eng, _prompts()) == reference
        st = eng.status()["kv_reuse"]
        assert st["spec_proposed"] > 0
        assert st["spec_accept_rate"] == 1.0
        snap = observability.snapshot()
        assert snap["paddle_tpu_decode_spec_accept_rate"]["series"][0][
            "value"] == 1.0
        # near-max_len: 10 new tokens from a 57-token prompt crosses
        # max_len-1=63 mid-way, demoting rounds to the plain path
        long_p = list(range(1, 58))
        want = _ref_stream(params, cfg, long_p, 6)
        got = eng.submit(long_p, max_new_tokens=6).result(timeout_s=180)
        assert got == want
    finally:
        eng.stop()


def _ref_stream(params, cfg, prompt, n):
    seq = list(prompt)
    out = []
    for _ in range(n):
        ids = np.asarray(np.array(seq, np.int32)[None])
        logits = gpt.apply(params, cfg, ids)
        t = int(np.argmax(np.asarray(logits[0, -1])))
        out.append(t)
        seq.append(t)
    return out


def test_spec_decode_bit_identical_real_draft(model, reference):
    """A DIFFERENT draft model (other init seed): proposals get
    rejected sometimes, yet rejection only costs batching — the
    emitted stream is still exactly the target's greedy output."""
    params, cfg = model
    dcfg = gpt.GPTConfig.tiny()
    dcfg.dtype = "float32"
    dparams, _ = gpt.init(jax.random.key(1), dcfg)
    eng = make_engine(model, prefill_chunk=8, spec_k=3,
                      draft=(dparams, dcfg))
    eng.warmup()
    try:
        assert _run(eng, _prompts()) == reference
        st = eng.status()["kv_reuse"]
        assert st["spec_proposed"] > 0
        assert 0.0 <= st["spec_accept_rate"] <= 1.0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Config / boot validation
# ---------------------------------------------------------------------------


def test_config_validation(model):
    params, cfg = model
    with pytest.raises(ValueError):
        DecodeConfig(prefix_cache=True)          # needs prefill_chunk
    with pytest.raises(ValueError):
        DecodeConfig(prefill_chunk=-1)
    with pytest.raises(ValueError):
        DecodeConfig(spec_k=-2)
    with pytest.raises(ValueError):              # spec needs a draft
        make_engine(model, spec_k=2, prefill_buckets=(8,))
    with pytest.raises(ValueError):              # draft needs spec_k
        make_engine(model, prefill_buckets=(8,), draft=(params, cfg))


def test_draft_cross_validation_findings(model, monkeypatch):
    """Draft/target mismatches land as analysis findings at boot (the
    PR 8 shape, var='draft'), not as garbage tokens at serve time."""
    monkeypatch.delenv("PADDLE_TPU_VALIDATE", raising=False)
    dcfg = gpt.GPTConfig.tiny()
    dcfg.dtype = "float32"
    dcfg.vocab_size += 1                   # ids meaningless to verifier
    dparams, _ = gpt.init(jax.random.key(2), dcfg)
    eng = make_engine(model, prefill_chunk=8, spec_k=2,
                      draft=(dparams, dcfg))
    assert eng.analysis["errors"] >= 1
    eng.stop()


# ---------------------------------------------------------------------------
# Warmstart: the re-keyed chunk+spec grid
# ---------------------------------------------------------------------------


def test_warmstart_rekeyed_grid_roundtrip(model, tmp_path):
    """With chunking the grid is re-keyed (chunk@C replaces every
    prefill@T; spec adds draft+verify phases) — the coldstart contract
    must hold for THAT grid: full adoption, zero fresh compiles,
    bit-identical tokens."""
    params, cfg = model
    kw = dict(prefill_chunk=8, prefix_cache=True, spec_k=2)
    cold = make_engine(model, draft=(params, cfg), **kw)
    assert cold.warmup() == 5     # chunk, decode, draft×2, verify
    art = str(tmp_path / "kvreuse.warmstart")
    assert cold.export_warmstart(art) == 5
    prompt = _prompts()[0]
    cold_toks = cold.submit(prompt, max_new_tokens=6).result(
        timeout_s=180)
    cold.stop()

    before = _compile_counts()
    warm = make_engine(model, draft=(params, cfg), warmstart=art, **kw)
    assert warm.warmstart_adopted == 5
    assert warm.warmup() == 5
    warm_toks = warm.submit(prompt, max_new_tokens=6).result(
        timeout_s=180)
    warm.stop()
    after = _compile_counts()
    fresh = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("prefill", "decode")}
    assert fresh == {"prefill": 0, "decode": 0}, fresh
    assert warm_toks == cold_toks
