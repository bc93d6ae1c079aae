"""MiniCPM-SALA (`paddle_tpu/models/minicpm_sala.py`) on the CPU at a tiny
size: the serve programs (a prompt walked in slices, then decode steps
through the paged K/V, the compressed-key entry and the state rows) against
the plain reference's full forward pass, with a `dense_len` so small that a
sequence crosses from the dense to the sparse read while it decodes; the
selection against the reference's brute-force one; the chunked lightning
scan against the token recurrence, and the row update against both; the
third cache entry, written at every `stride`-th token by prefill and decode
alike and freed with its blocks; the engine end to end (preemption and
replay, a row another sequence has just freed); and the block-sparse walk's
kernel through the Pallas TPU interpreter."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.reference import minicpm_sala_ref as ref
from paddle_tpu.models import decoder, minicpm_sala as M
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import ssm_update as SU
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

BS = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = M.MiniCPMSALAConfig.tiny()
    params, _ = M.init(jax.random.key(0), cfg)
    return cfg, params, dataclasses.asdict(cfg)


def test_the_pattern_and_the_pools_are_the_models(tiny):
    cfg, params, _ = tiny
    sm = cfg.serve_model()
    assert cfg.pattern == "*EMEME*E" and sm.layers == 8
    assert M.MiniCPMSALAConfig().mixers.count("S") == 8 \
        and len(M.MiniCPMSALAConfig().mixers) == 32
    assert sm.kv_layers == 2 and sm.stored == (32, 32)
    assert sm.rated == ((32, 4),) and sm.prompt_slice == 32
    (shape, dt), = sm.state_pools(5, jnp.bfloat16)
    assert shape == (2, 5, 4, 16, 16) and dt == jnp.float32
    assert cfg.depth_scale == pytest.approx(1.4 / math.sqrt(32))
    assert params["attn.wq"].shape == (2, 64, 64)
    assert params["lin.wk"].shape == (2, 64, 64)
    assert params["mlp.w_down"].shape == (4, 128, 64)
    # a score's deviation: the product of the two QK-norm gains
    gains = params["attn.q_norm"] * params["attn.k_norm"]
    assert 3.0 < float(jnp.mean(gains)) < 7.0
    with pytest.raises(ValueError, match="two strides"):
        M.MiniCPMSALAConfig(kernel_size=48)


# -- the programs against the reference --------------------------------------


class _Probe(M.MiniCPMSALAServe):
    """The model with its logits kept: what is compared is logits, not
    sampled tokens."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.logits = []

    def head(self, params, x, prev_ids, eos_id):
        from paddle_tpu.models.common import rms_norm

        c = self.cfg
        x = rms_norm(x, params["ln_f.scale"], c.rms_eps) \
            * (c.dim_model_base / c.hidden)
        logits = x @ params["head.w"]
        self.logits.append(logits)
        return jnp.argmax(logits, -1).astype(jnp.int32)


def _pools(sm, cfg, blocks=40, rows=3):
    kv = kvc.KVCacheConfig(
        layers=sm.kv_layers, widths=sm.stored, max_len=cfg.max_len,
        block_size=BS, num_blocks=blocks, dtype="float32", rated=sm.rated)
    state = tuple(jnp.zeros(s, d) for s, d in
                  sm.state_pools(rows, jnp.float32)) \
        + kvc.init_rated_pools(kv)
    return kv, kvc.init_pools(kv), state


def test_prefill_then_decode_equal_the_reference_across_the_crossing(tiny):
    """A prompt of 40 tokens in a bucket of 64 (two slices of 32, the
    second half padding), then 60 decode steps: positions 40..99, over
    `dense_len` 48 from the ninth step on. Float32 throughout, so the
    tolerance is rounding in another order of summation: 1e-4 of logits
    whose deviation is 1."""
    cfg, params, model = tiny
    sm = _Probe(cfg)
    kv, (kp, vp), state = _pools(sm, cfg)
    rng = np.random.default_rng(1)
    n_prompt, n_new, bucket = 40, 60, 64
    ids = rng.integers(0, cfg.vocab_size, size=n_prompt + n_new)
    blocks = list(range(5, 5 + kv.max_blocks_per_seq))
    rng.shuffle(blocks)
    bt = kvc.build_block_table(blocks, kv.max_blocks_per_seq)
    padded = np.full((1, bucket), ids[n_prompt - 1], np.int32)
    padded[0, :n_prompt] = ids[:n_prompt]
    kw = dict(block_size=BS, eos_id=-1)
    _, kp, vp, state = decoder.prefill(
        sm, params, jnp.asarray(padded), jnp.int32(n_prompt), kp, vp,
        jnp.asarray(bt), state, jnp.int32(1), **kw)
    got = [np.asarray(sm.logits[-1][0])]
    bts = jnp.asarray(np.stack([bt, np.zeros_like(bt)]))
    rows = jnp.asarray([1, 0], jnp.int32)
    facts = []
    for t in range(n_prompt, n_prompt + n_new - 1):
        sm.logits.clear()
        out = decoder.decode_step(
            sm, params, jnp.asarray([ids[t], 0], jnp.int32),
            jnp.asarray([t, 0], jnp.int32), kp, vp, bts, state, rows, **kw)
        _, kp, vp, stats, state = out
        got.append(np.asarray(sm.logits[-1][0]))
        facts.append(sm.step_facts(jax.device_get(stats)))
    width = -(-(n_prompt + n_new) // 128) * 128
    full = np.zeros((width,), np.int32)
    full[:n_prompt + n_new] = ids
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits_rows(
            params, model, jnp.asarray(full), n_prompt - 1, n_new))
    assert want.std() > 0.5
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)
    # the step's counters: a dense row until 48 tokens, then a sparse one
    # that reads topk = 4 blocks and scores n // 4 - 1 compressed keys
    assert [f["sparse_rows"] for f in facts[:8]] == [0] * 8
    assert facts[0]["dense_tokens"] == 41
    assert all(f["sparse_rows"] == 1 and f["blocks_selected"] == 4.0
               and f["dense_tokens"] == 0 for f in facts[8:])
    assert facts[-1]["kc_entries"] == 99 // 4 - 1


@pytest.mark.parametrize("fault, least", [
    ("dense_walk", 1e-2), ("sparse_rope", 1e-2), ("lin_rope", 1e-2),
    ("decay_one", 1e-2), ("bf16_state_layer", 1e-4)])
def test_the_references_switches_change_its_answer(tiny, fault, least):
    """Each control of the cell's tolerance is a different model: the
    reference with the switch set moves the logits of a sequence that is
    over `dense_len`."""
    cfg, params, model = tiny
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=128), jnp.int32)
    value = {"lin_rope": False, "bf16_state_layer": 1}.get(fault, True)
    with jax.default_matmul_precision("highest"):
        right = ref.logits_rows(params, model, ids, 100, 16)
        wrong = ref.logits_rows(params, dict(model, **{fault: value}), ids,
                                100, 16)
    assert float(jnp.max(jnp.abs(right - wrong))) > least


# -- the selection -----------------------------------------------------------


def _brute(cfg, q, k, n):
    """The blocks one query (q [G, R, D]) takes over the keys k [n, G, D],
    written out with loops."""
    G, R, D = q.shape
    stride, kernel, sb = cfg.kernel_stride, cfg.kernel_size, cfg.sel_block
    nsb = (n - 1) // sb + 1
    taken = np.zeros((G, nsb), bool)
    for g in range(G):
        windows = [j for j in range(n) if stride * j + kernel <= n]
        score = np.zeros(nsb)
        if windows:
            kc = np.stack([k[stride * j:stride * j + kernel, g].mean(0)
                           for j in windows])
            P = np.zeros(len(windows))
            for r in range(R):
                s = kc @ q[g, r] / math.sqrt(D)
                e = np.exp(s - s.max())
                P += e / e.sum()
            for b in range(nsb):
                over = [P[i] for i, j in enumerate(windows)
                        if stride * j < sb * (b + 1)
                        and stride * j + kernel > sb * b]
                score[b] = max(over, default=0.0)
        forced = [b for b in range(nsb) if b < cfg.init_blocks
                  or b >= max(n - cfg.window, 0) // sb]
        rest = sorted((b for b in range(nsb) if b not in forced),
                      key=lambda b: (-score[b], b))
        for b in (forced + rest)[:max(cfg.topk, 0)]:
            taken[g, b] = True
    return taken


def _compressed(cfg, k, blocks_of, bs):
    """The compressed keys of k [T, G, D] as the third pool's rows hold
    them: entry e (the window that completes in group e) in block e * stride
    // bs, `bs // stride` entries a row."""
    T, G, D = k.shape
    stride = cfg.kernel_stride
    per = bs // stride
    rows = np.zeros((blocks_of, per * G * D), np.float32)
    for e in range(1, T // stride):
        kc = k[stride * (e - 1):stride * (e + 1)].mean(0).reshape(-1)
        rows[e // per, (e % per) * G * D:(e % per + 1) * G * D] = kc
    return rows


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("n", [49, 63, 64, 65, 90, 127, 128])
def test_the_selection_is_the_brute_force_one(n, bs):
    """Forced blocks (the first, the newest 32 tokens'), an incomplete last
    window, the heads of a K/V head selecting together, and cache blocks of
    half a selection block and of a whole one."""
    cfg = M.MiniCPMSALAConfig.tiny()
    G, R, D = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
    rng = np.random.default_rng(n)
    T = 128
    q = rng.normal(size=(3, G, R, D)).astype(np.float32) * 2.0
    k = rng.normal(size=(T, G, D)).astype(np.float32)
    rows = _compressed(cfg, k, T // bs, bs)
    ns = np.asarray([n, max(n - 20, 1), n])
    mask = np.asarray(M.select_blocks(
        cfg, jnp.asarray(q), jnp.asarray(rows), jnp.asarray(ns), bs))
    nsb = T // cfg.sel_block
    for i, ni in enumerate(ns):
        want = _brute(cfg, q[i], k, int(ni))
        assert (mask[i][:, :want.shape[1]] == want).all(), (i, ni)
        assert not mask[i][:, want.shape[1]:].any()
    # as indices: ascending, the missing ones past the last block
    idx = np.asarray(M.taken_indices(jnp.asarray(mask), cfg.topk))
    assert idx.shape == (3, cfg.kv_heads, cfg.topk)
    assert (np.diff(idx, axis=-1) >= 0).all()
    for i in range(3):
        for g in range(cfg.kv_heads):
            assert idx[i, g].tolist() == sorted(
                np.flatnonzero(mask[i, g]).tolist()) \
                + [nsb] * (cfg.topk - int(mask[i, g].sum()))
    # the same with a table a slot (the decode step's form)
    mask2 = M.select_blocks(cfg, jnp.asarray(q),
                            jnp.broadcast_to(rows, (3,) + rows.shape),
                            jnp.asarray(ns), bs)
    assert (np.asarray(mask2) == mask).all()


def _select_where_they_lie(cfg, q, rows, n, bs):
    """`select_blocks` by the kernel's route: the compressed keys `rows`
    `[MB, E * W]` put into a pool at ids that do not follow each other,
    scored there through the table (the Pallas TPU interpreter)."""
    mb, lanes = rows.shape
    ids = 3 + 5 * np.arange(mb)
    pool = np.zeros((1, 160, lanes), np.float32)
    pool[0, ids] = rows
    score = PA.paged_select_scores(
        jnp.asarray(q.reshape(1, -1)), jnp.asarray(pool), jnp.int32(0),
        PA.with_rows(PA.Tables(jnp.asarray(ids[None], jnp.int32), None),
                     jnp.asarray([n - 1], jnp.int32), bs),
        jnp.asarray([n - 1], jnp.int32),
        kv_heads=cfg.kv_heads, stride=cfg.kernel_stride, block_size=bs,
        interpret=pltpu.InterpretParams())
    return M.take_blocks(cfg, score, jnp.asarray([n]))


@pytest.mark.parametrize("route", ["gathered", "where_they_lie"])
def test_ties_go_to_the_lower_block_and_a_group_selects_together(route):
    """Keys that are all alike score every block alike: the rest of the
    top-k is the lowest blocks; and the two K/V heads of one query pick
    their own blocks, every query head of a K/V head the same ones. By
    the gathered keys and by the kernel that reads them where they lie."""
    cfg = M.MiniCPMSALAConfig.tiny()
    G, R, D = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
    T, bs, n = 128, 16, 128

    def select(q, k):
        rows = _compressed(cfg, k, T // bs, bs)
        if route == "where_they_lie":
            return _select_where_they_lie(cfg, q, rows, n, bs)
        return M.select_blocks(cfg, jnp.asarray(q), jnp.asarray(rows),
                               jnp.asarray([n]), bs)

    mask = select(np.ones((1, G, R, D), np.float32),
                  np.ones((T, G, D), np.float32))
    # block 0 (init), blocks 6 and 7 (the newest 32 tokens), then the lowest
    assert np.asarray(M.taken_indices(mask, 4)).tolist() \
        == [[[0, 1, 6, 7]] * G]
    rng = np.random.default_rng(5)
    k = rng.normal(size=(T, G, D)).astype(np.float32)
    k[40, 0] *= 30.0        # K/V head 0's block 2 stands out for head 0
    q = np.broadcast_to(k[40][None, :, None, :], (1, G, R, D)).copy()
    assert bool(select(q, k)[0, 0, 2])


def test_the_top_mask_is_the_k_largest_with_ties_to_the_lower_index():
    rng = np.random.default_rng(9)
    score = rng.choice([0.0, 0.25, 0.5, 1.0, 3.0, 1e4], size=(50, 40)) \
        .astype(np.float32)
    exists = rng.random((50, 40)) < 0.8
    exists[0] = False
    exists[1, 5:] = False
    for k in (1, 4, 7):
        got = np.asarray(M.top_mask(jnp.asarray(score), jnp.asarray(exists),
                                    k))
        for r in range(50):
            order = sorted(np.flatnonzero(exists[r]),
                           key=lambda b: (-score[r, b], b))[:k]
            assert sorted(np.flatnonzero(got[r])) == sorted(order), (k, r)


# -- the lightning layers ----------------------------------------------------


def test_the_chunked_scan_the_recurrence_and_the_row_update_agree():
    """`ssd_chunked` told a fixed decay a head (`linear_attention_args`) is
    the token recurrence `S = exp(-s) S + v k^T, o = S q`, a prompt cut in
    two slices carries its state, padding leaves it, and the in-place row
    update (the kernel, through the interpreter) continues it."""
    rng = np.random.default_rng(3)
    H, D, T = 8, 128, 24
    slopes = jnp.exp2(-8.0 * jnp.arange(1, H + 1) / H)
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, H, D)), jnp.float32)
               for _ in range(3))
    want_state = np.zeros((H, D, D), np.float32)
    want = []
    for t in range(T):
        want_state = np.exp(-np.asarray(slopes))[:, None, None] \
            * want_state + np.asarray(v[0, t])[:, :, None] \
            * np.asarray(k[0, t])[:, None, :]
        want.append((want_state * np.asarray(q[0, t])[:, None, :]).sum(-1))
    live = jnp.ones((1, T), bool)
    dt, A, Dz = ssm.linear_attention_args(slopes, live)
    out, state = ssm.ssd_chunked(v, dt, A, k, q, Dz, 8)
    np.testing.assert_allclose(out[0], np.stack(want), atol=2e-4)
    np.testing.assert_allclose(state[0], want_state, atol=2e-4)
    # two slices, the second padded: positions past 20 do not count
    cut, length = 16, 20
    dt1, _, _ = ssm.linear_attention_args(slopes, live[:, :cut])
    _, s1 = ssm.ssd_chunked(v[:, :cut], dt1, A, k[:, :cut], q[:, :cut], Dz,
                            8)
    dt2, _, _ = ssm.linear_attention_args(
        slopes, (jnp.arange(cut, T) < length)[None])
    out2, s2 = ssm.ssd_chunked(v[:, cut:], dt2, A, k[:, cut:], q[:, cut:],
                               Dz, 8, s1)
    np.testing.assert_allclose(out2[0, :length - cut],
                               np.stack(want)[cut:length], atol=2e-4)
    out20, s20 = ssm.ssd_chunked(v[:, :length], dt[:, :length], A,
                                 k[:, :length], q[:, :length], Dz, 8)
    np.testing.assert_allclose(s2, s20, atol=2e-4)
    # one more token: `ssd_step`, and the kernel on a pool of rows
    nq, nk, nv = (jnp.asarray(rng.normal(size=(1, H, D)), jnp.float32)
                  for _ in range(3))
    one = jnp.ones((1, H), jnp.float32)
    o_step, s_step = ssm.ssd_step(s20, nv, one, A, nk, nq, Dz)
    pool = jnp.zeros((2, 3, H, D, D), jnp.float32).at[1, 2].set(s20[0])
    assert SU._heads_per_block(pool) % 1 == 0
    o_k, pool = SU.state_update(
        pool, jnp.int32(1), jnp.asarray([2], jnp.int32), jnp.exp(one * A),
        nv, nk, nq, interpret=pltpu.InterpretParams())
    np.testing.assert_allclose(o_k, o_step, atol=2e-4)
    np.testing.assert_allclose(pool[1, 2], s_step[0], atol=2e-4)
    assert not np.asarray(pool[0]).any() and not np.asarray(pool[1, :2]).any()


# -- the third cache entry ---------------------------------------------------


def test_the_third_entry_follows_the_table_and_counts_in_the_bytes():
    kv = kvc.KVCacheConfig(layers=2, max_len=64, block_size=16,
                           num_blocks=9, widths=(256, 256),
                           rated=((256, 16),))
    assert kv.rated_pool_shapes == ((2, 16, 256),)
    assert kv.bytes_per_token() == (512 + 16) * 2
    assert kv.walk_bytes_per_token() == 1024
    assert kv.pool_bytes() == 2 * 9 * (2 * 16 * 256 + 256) * 2
    stats = kvc.BlockAllocator(kv).stats()
    assert stats["rated_entries"] == [
        {"width": 256, "stride": 16, "bytes_per_token_layer": 32.0}]
    assert stats["bytes_per_token_layer"] == 1056
    plain = kvc.KVCacheConfig(layers=2, max_len=64, widths=(256, 256))
    assert plain.bytes_per_token() == 1024 and not plain.rated_pool_shapes
    with pytest.raises(ValueError, match="whole strides"):
        kvc.KVCacheConfig(layers=1, max_len=64, block_size=8,
                          widths=(8, 8), rated=((8, 16),))
    # one entry a slot where it is due, the null block where it is not
    cfg4 = kvc.KVCacheConfig(layers=1, max_len=64, block_size=8,
                             num_blocks=6, widths=(4, 4), rated=((4, 4),),
                             dtype="float32")
    (pool,) = kvc.init_rated_pools(cfg4)
    tables = jnp.asarray([[3, 5, 0], [2, 0, 0]], jnp.int32)
    pool = kvc.write_token_rated(
        pool, jnp.int32(0), jnp.asarray([[1., 2, 3, 4], [5, 6, 7, 8]]),
        tables, jnp.asarray([3, 1]), jnp.asarray([True, False]), 2)
    assert np.asarray(pool[0, 5]).tolist() == [0, 0, 0, 0, 1, 2, 3, 4]
    assert not np.asarray(pool[0, 2]).any() \
        and not np.asarray(pool[0, 3]).any()
    pool = kvc.write_blocks_rated(
        pool, jnp.int32(0), jnp.arange(16.).reshape(4, 4),
        jnp.asarray([4, 1]))
    assert np.asarray(pool[0, 1]).tolist() == list(range(8, 16))
    got = kvc.gather_rated(pool, jnp.int32(0), jnp.asarray([[4, 1]]))
    assert got.shape == (1, 2, 8) and np.asarray(got[0, 0]).tolist() \
        == list(range(8))


def test_prefill_and_decode_write_the_same_compressed_keys(tiny):
    """The third pool after a prompt of 64 tokens equals the pool after a
    prompt of 32 and 32 decode steps over the same tokens, entry by entry:
    the mean of the 8 newest cached keys every 4th token, in the blocks of
    the sequence's table."""
    cfg, params, _ = tiny
    sm = cfg.serve_model()
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, size=64)
    kw = dict(block_size=BS, eos_id=-1)
    pools = {}
    for n_prompt in (64, 32):
        kv, (kp, vp), state = _pools(sm, cfg)
        bt = kvc.build_block_table(list(range(9, 9 + 8)),
                                   kv.max_blocks_per_seq)
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n_prompt] = ids[:n_prompt]
        _, kp, vp, state = decoder.prefill(
            sm, params, jnp.asarray(padded), jnp.int32(n_prompt), kp, vp,
            jnp.asarray(bt), state, jnp.int32(1), **kw)
        for t in range(n_prompt, 64):
            _, kp, vp, _, state = decoder.decode_step(
                sm, params, jnp.asarray([ids[t]], jnp.int32),
                jnp.asarray([t], jnp.int32), kp, vp, jnp.asarray(bt[None]),
                state, jnp.asarray([1], jnp.int32), **kw)
        pools[n_prompt] = (np.asarray(kp), np.asarray(state[-1]))
    keys, by_prefill = pools[64]
    _, by_decode = pools[32]
    per = BS // cfg.kernel_stride
    W = cfg.kv_heads * cfg.head_dim
    for layer in range(2):
        cached = keys[layer, 9:17].reshape(64, W)
        for e in range(1, 16):
            row, at = 9 + e // per, (e % per) * W
            want = cached[4 * (e - 1):4 * (e + 1)].mean(0)
            np.testing.assert_allclose(by_prefill[layer, row, at:at + W],
                                       want, atol=1e-6)
            np.testing.assert_allclose(by_decode[layer, row, at:at + W],
                                       want, atol=1e-5)
    # nothing outside the sequence's blocks but the null block was written
    assert not by_prefill[:, 1:9].any() and not by_prefill[:, 17:].any()


# -- the engine --------------------------------------------------------------


def _engine(cfg, params, **over):
    kw = dict(block_size=BS, num_blocks=4 * 24 + 1, decode_slots=(4,),
              prefill_buckets=(32, 64, 96), max_len=192, precision="f32")
    kw.update(over)
    return DecodeEngine(params, cfg, DecodeConfig(**kw))


def test_the_engine_serves_it_and_reports_the_third_entry(tiny):
    cfg, params, _ = tiny
    PA.GATE_COUNTS.clear()
    eng = _engine(cfg, params)
    status = eng.status()
    assert status["kv"]["entry_widths"] == [32, 32]
    assert status["kv"]["rated_entries"] == [
        {"width": 32, "stride": 4, "bytes_per_token_layer": 32.0}]
    assert status["kv"]["bytes_per_token_layer"] == (64 + 8) * 4
    assert status["kv"]["pool_bytes"] == eng.kv_cfg.pool_bytes() \
        == 2 * 97 * (2 * 8 * 32 + 2 * 32) * 4
    assert status["state"]["rows"] == 4
    assert status["state"]["bytes"] == 2 * 5 * 4 * 16 * 16 * 4
    eng.start()
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 512, size=n).tolist()
                   for n in (40, 70, 33)]
        alone = [eng.submit(p, max_new_tokens=40).result(300)
                 for p in prompts]
        # together, and in rows and blocks the first round has just freed
        handles = [eng.submit(p, max_new_tokens=40) for p in prompts]
        assert [h.result(300) for h in handles] == alone
        status = eng.status()
        # off the chip the compressed keys are gathered, never walked
        assert status["decode_attention"] == {"gather": 1,
                                              "select_gather": 1}
        assert status["state"]["update"].get("xla", 0) >= 2
        assert status["kv"]["blocks_used"] == 0 \
            and status["state"]["used"] == 0
    finally:
        eng.stop()
    for bad in (dict(prefix_cache=True, prefill_chunk=16),
                dict(prefill_chunk=16)):
        with pytest.raises(ValueError, match="cannot be served with"):
            _engine(cfg, params, **bad)


def test_a_preempted_sequence_replays_to_the_same_tokens(tiny):
    """A pool too small for two sequences to finish side by side: one is
    preempted, its blocks (K/V and compressed keys) and its state row
    freed, and its replay's prefill rebuilds them: both get the tokens they
    get alone."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 512, size=n).tolist() for n in (60, 62)]
    buckets = (32, 64, 96, 128)     # a replay's prompt is prompt + generated
    roomy = _engine(cfg, params, prefill_buckets=buckets)
    roomy.start()
    try:
        alone = [roomy.submit(p, max_new_tokens=40).result(300)
                 for p in prompts]
    finally:
        roomy.stop()
    # 21 blocks, 168 tokens: both are admitted (16 blocks) and cannot both
    # grow to their 13
    tight = _engine(cfg, params, prefill_buckets=buckets, num_blocks=22)
    tight.start()
    try:
        handles = [tight.submit(p, max_new_tokens=40) for p in prompts]
        assert [h.result(600) for h in handles] == alone
        assert tight.status()["requests"]["preempted"] >= 1
        assert tight.status()["kv"]["blocks_used"] == 0 \
            and tight.status()["state"]["used"] == 0
    finally:
        tight.stop()


# -- the block-sparse walk's kernel, through the interpreter -----------------


@pytest.mark.parametrize("bs", [16, 64])
def test_the_sparse_walk_reads_each_pairs_own_blocks(bs):
    """(slot, K/V head) pairs with lists of their own: a sparse pair's
    scattered blocks with a partial newest one, a pair that reads its whole
    table, a run of consecutive ids, an inactive slot: against plain
    attention over the tokens the lists name, one K/V head's lanes at a
    time."""
    L, S, G, R, D = 2, 3, 2, 16, 128
    NB, width = 40, 12
    rng = np.random.default_rng(bs)
    k_pool = jnp.asarray(rng.normal(size=(L, NB, bs, G * D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(L, NB, bs, G * D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(S, G * R * D)), jnp.bfloat16)
    tables = np.zeros((S * G, width), np.int32)
    newest = np.zeros((S * G,), np.int32)
    tables[0, :5] = [7, 3, 30, 31, 32]          # scattered, then a run
    newest[0] = 4 * bs + bs // 2
    tables[1, :12] = np.arange(20, 32)          # one run, all of the width
    newest[1] = 12 * bs - 1
    tables[2, :1] = [9]                         # one token
    newest[2] = 0
    tables[3, :3] = [2, 39, 1]
    newest[3] = 2 * bs + 3
    # pairs 4 and 5: an inactive slot
    got = PA.paged_sparse_attention(
        q, k_pool, v_pool, jnp.int32(1), jnp.asarray(tables),
        jnp.asarray(newest), heads=G * R, kv_heads=G,
        interpret=pltpu.InterpretParams())
    got = np.asarray(got, np.float32).reshape(S, G, R, D)
    qf = np.asarray(q, np.float32).reshape(S, G, R, D)
    for pair in range(S * G):
        s, g = divmod(pair, G)
        if not tables[pair, 0]:
            assert not got[s, g].any()
            continue
        n = newest[pair] + 1
        blocks = tables[pair, :-(-n // bs)]
        keys = np.asarray(k_pool, np.float32)[1, blocks].reshape(
            -1, G, D)[:n, g]
        vals = np.asarray(v_pool, np.float32)[1, blocks].reshape(
            -1, G, D)[:n, g]
        sc = qf[s, g] @ keys.T / math.sqrt(D)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ vals
        np.testing.assert_allclose(got[s, g], want, atol=3e-2, rtol=3e-2)


def _runs(*spans):
    """Block ids: `(first, count)` a run."""
    return np.concatenate([np.arange(a, a + n) for a, n in spans])


# tables (block ids a slot; () an inactive slot) of a pool of `nb` blocks
# and the tokens a slot sees
_SELECT_CASES = {
    # one run a table: under a piece, over one, over two; the last ends
    # with the pool (its copy starts before its first block)
    "one_run": lambda nb: (
        [_runs((3, 45)), _runs((60, 130)), _runs((nb - 254, 254))],
        [45 * 64 - 7, 130 * 64 - 30, 254 * 64 - 1]),
    "scattered": lambda nb: (
        [np.random.default_rng(1).permutation(np.arange(1, nb))[:n]
         for n in (41, 33)], [41 * 64 - 3, 33 * 64 - 20]),
    # runs and single blocks, a run over a piece between them, one that
    # starts on a tile and the pool's last block
    "mixed": lambda nb: (
        [_runs((9, 50), (400, 1), (7, 1), (100, 125), (nb - 1, 1), (64, 16)),
         _runs((300, 3), (2, 1), (310, 70))], [194 * 64 - 5, 74 * 64 - 33]),
    # a row that ends exactly with a block, one whose last window is
    # incomplete, one at its first block's second token, short and long
    "unequal": lambda nb: (
        [_runs((20, 40)), _runs((70, 9), (90, 150)), _runs((5, 1)),
         _runs((250, 121))],
        [40 * 64, 158 * 64 + 42, 2, 121 * 64 - 16 - 1]),
    # a slot whose table starts with the null block, between live ones
    "inactive": lambda nb: (
        [_runs((30, 20)), (), _runs((200, 5), (60, 125)), (),
         _runs((400, 33))], [20 * 64 - 1, 777, 130 * 64 - 40, 0, 33 * 64 - 9]),
    # pieces a short copy holds (24 blocks at most) and one block more,
    # across a tile of the scores, at the pool's end, after a long piece
    "short_runs": lambda nb: (
        [_runs((17, 24), (50, 25), (90, 7), (nb - 24, 24), (130, 60)),
         _runs((201, 120), (5, 3), (330, 24), (9, 1), (400, 8)),
         _runs(*[(10 + 9 * i, 8) for i in range(20)])],
        [140 * 64 - 9, 156 * 64 - 2, 160 * 64 - 31]),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(_SELECT_CASES))
def test_the_selection_scores_the_compressed_keys_where_they_lie(case, dtype):
    """`paged_select_scores` (the Pallas TPU interpreter) against
    `block_scores` on the gathered keys: the same block scores to float32
    rounding whatever the pieces its walk cut the table into and whichever
    copy, the long or the short, fetched them, nothing for an inactive
    slot, and the same taken blocks wherever the last block taken and the
    first one left differ by more than that rounding; over a pool of
    2-byte and of 4-byte lanes."""
    nb, dtype = 512, jnp.dtype(dtype)
    G, R, D, bs, stride = 2, 16, 128, 64, 16
    cfg = M.MiniCPMSALAConfig(
        mixers="S", heads=G * R, kv_heads=G, head_dim=D, kernel_size=32,
        kernel_stride=stride, sel_block=bs, topk=12, init_blocks=1,
        window=256, dense_len=512)
    lists, seen = _SELECT_CASES[case](nb)
    S, MB = len(lists), 260
    rng = np.random.default_rng(len(case))
    pool = jnp.asarray(rng.normal(size=(2, nb, (bs // stride) * G * D)),
                       dtype)
    q = jnp.asarray(rng.normal(size=(S, G * R * D)) * 2.0, dtype)
    tables = np.zeros((S, MB), np.int32)
    for s, ids in enumerate(lists):
        tables[s, :len(ids)] = ids
    n = jnp.asarray(seen, jnp.int32)
    got = PA.paged_select_scores(
        q, pool, jnp.int32(1),
        PA.with_rows(PA.Tables(jnp.asarray(tables), None), n - 1, bs),
        n - 1, kv_heads=G, stride=stride, block_size=bs,
        interpret=pltpu.InterpretParams())
    want = M.block_scores(
        cfg, q.reshape(S, G, R, D),
        kvc.gather_rated(pool, jnp.int32(1), jnp.asarray(tables)), n, bs)
    live = np.asarray([len(ids) > 0 for ids in lists])
    assert got.shape == (S, G, MB) and got.dtype == jnp.float32
    assert not np.asarray(got)[~live].any()
    tol = 1e-5
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=tol, atol=tol * 1e-2)
    assert np.asarray(want)[live].max() > 0.05      # not all alike
    taken = np.asarray(M.take_blocks(cfg, got, n))
    taken_ref = np.asarray(M.take_blocks(cfg, want, n))
    compared = 0
    for s in np.flatnonzero(live):
        b = np.arange(MB)
        exists = b <= (seen[s] - 1) // bs
        forced = (b < cfg.init_blocks) \
            | (b >= max(seen[s] - cfg.window, 0) // bs)
        for g in range(G):
            # the scores the top-k chooses among, the largest first
            free = np.sort(np.asarray(want)[s, g][exists & ~forced])[::-1]
            left = int(taken_ref[s, g].sum() - (exists & forced).sum())
            if 0 < left < len(free) and \
                    free[left - 1] - free[left] <= 10 * tol * free[left - 1]:
                continue        # a near tie: either block is right
            assert (taken[s, g] == taken_ref[s, g]).all(), (s, g)
            compared += 1
    assert compared >= G * live.sum() - 1


@pytest.mark.parametrize("mb", [5, 32, 33, 100, 768])
def test_the_pieces_of_a_table_are_its_runs_cut_at_a_copys_blocks(mb):
    """`Tables.rows`, what the selection's walk goes by: of every entry of
    a table, how many ids from it on follow each other, the 120 blocks at
    most that a copy of 128 rows from a tile's first holds, against a
    count by hand; the null entries past a sequence are pieces of one."""
    rng = np.random.default_rng(mb)
    ids = np.zeros((6, mb), np.int32)
    for s in range(6):
        j = 0
        while j < mb - 3:
            k = min(int(rng.integers(1, 300)), mb - 3 - j)
            ids[s, j:j + k] = int(rng.integers(1, 100000)) + np.arange(k)
            j += k
    want = np.ones_like(ids)
    for j in range(mb - 2, -1, -1):
        want[:, j] = np.where(ids[:, j + 1] == ids[:, j] + 1,
                              want[:, j + 1] + 1, 1)
    live = rng.integers(0, mb - 2, size=6)
    tables = PA.with_rows(PA.Tables(jnp.asarray(ids), None),
                          jnp.asarray(live * 64 - 1), 64)
    assert (np.asarray(tables.rows) == np.minimum(want, 120)).all()
    # `few`: the pieces the walk takes of the live blocks (counted as the
    # live runs and a cut every 120 blocks: never under the walk's own
    # count), a piece the price of 20 entries of the gathered tables
    walked = counted = 0
    for s in range(6):
        j = 0
        while j < live[s]:
            j += min(want[s, j], 120, live[s] - j)
            walked += 1
        counted += 1 * (live[s] > 0) + live[s] // 120 + sum(
            ids[s, j] != ids[s, j - 1] + 1 for j in range(1, live[s]))
    assert walked <= counted <= walked + sum(live // 120)
    assert bool(tables.few) == (counted * 20 <= 6 * mb)


@pytest.mark.parametrize("runs,few", [(120, True), (40, True), (2, False),
                                      (1, False)])
def test_a_table_in_too_many_pieces_is_gathered_and_scores_the_same(runs,
                                                                     few):
    """`scores_where_they_lie`: the kernel (the Pallas TPU interpreter)
    where the live blocks are few pieces, the gather of the whole tables
    where the pool has fragmented into short runs, chosen in the program
    by `Tables.few`; `block_scores` either way."""
    G, R, D, bs, stride, S, MB, nb = 2, 16, 128, 64, 16, 3, 200, 1024
    cfg = M.MiniCPMSALAConfig(
        mixers="S", heads=G * R, kv_heads=G, head_dim=D, kernel_size=32,
        kernel_stride=stride, sel_block=bs, topk=12, init_blocks=1,
        window=256, dense_len=512)
    rng = np.random.default_rng(runs)
    pool = jnp.asarray(rng.normal(size=(2, nb, 4 * G * D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(S, G * R * D)) * 2.0, jnp.bfloat16)
    first = 1 + (runs + 1) * rng.permutation((nb - 2) // (runs + 1))
    live = np.asarray([150, 97, 121])
    ids = np.zeros((S, MB), np.int32)
    taken = 0
    for s in range(S):
        for j in range(0, live[s], runs):
            k = min(runs, live[s] - j)
            ids[s, j:j + k] = first[taken] + np.arange(k)
            taken += 1
    pos = jnp.asarray(live * bs - 5, jnp.int32)
    tables = PA.with_rows(PA.Tables(jnp.asarray(ids), None), pos, bs)
    assert bool(tables.few) == few
    got = cfg.serve_model().scores_where_they_lie(
        q, pool, jnp.int32(1), tables, pos, bs,
        interpret=pltpu.InterpretParams())
    want = M.block_scores(cfg, q.reshape(S, G, R, D),
                          kvc.gather_rated(pool, jnp.int32(1),
                                           jnp.asarray(ids)), pos + 1, bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


# (what differs from the cell's call, the gate's answer on a TPU)
_SELECT_GATE_CASES = {
    "the_cells_shapes": ({}, True),
    "a_selection_block_of_two_cache_blocks": ({"per_sel": 2}, False),
    "a_row_of_half_lane_tiles": ({"pool": (1, 200, 4 * 2 * 64)}, False),
    "three_entries_a_block": ({"per_block": 3}, False),
    "eight_query_heads_a_group": ({"heads": 16}, False),
    "another_dtype_than_the_queries": ({"dtype": jnp.float32}, False),
    "a_pool_under_one_copys_rows": ({"pool": (1, 96, 1024)}, False),
    "blocks_that_are_not_whole_tiles": ({"pool": (1, 203, 1024)}, False),
    "tables_over_the_scalar_memory": (
        {"slots": 64, "max_blocks": 1536}, False),
    "scores_over_the_vector_memory": ({"max_blocks": 16384}, False),
}


@pytest.mark.parametrize("case", list(_SELECT_GATE_CASES))
def test_the_selections_gate_asks_for_a_row_a_block_of_whole_lane_tiles(
        case, monkeypatch):
    """`use_paged_select`: shut off a TPU whatever the shapes; on one,
    open for the cell's shapes and shut for each thing the kernel cannot
    take, a table or scores its memories do not hold among them."""
    change, want = _SELECT_GATE_CASES[case]
    q = jnp.zeros((change.get("slots", 2), 32 * 128), jnp.bfloat16)
    pool = jnp.zeros(change.get("pool", (1, 200, 4 * 2 * 128)),
                     change.get("dtype", jnp.bfloat16))
    args = (q, pool, change.get("heads", 32), 2, change.get("per_block", 4),
            change.get("per_sel", 1), change.get("max_blocks", 768))
    assert not PA.use_paged_select(*args)
    monkeypatch.setattr(PA, "_on_one_tpu", lambda x: True)
    assert PA.use_paged_select(*args) == want


def test_the_model_counts_how_a_step_reads_its_compressed_keys():
    """`rated_tables`: asked once a step, counted in `GATE_COUNTS`; off a
    TPU the tables come back as they are and the layers gather."""
    cfg = M.MiniCPMSALAConfig.tiny()
    PA.GATE_COUNTS.clear()
    tables = PA.Tables(jnp.zeros((2, 4), jnp.int32), None)
    same = cfg.serve_model().rated_tables(
        jnp.zeros((2, 64)), tables, (jnp.zeros((1, 16, 64)),),
        jnp.zeros((2,), jnp.int32), 8)
    assert same is tables and PA.GATE_COUNTS == {"select_gather": 1}


def test_the_gate_asks_for_whole_tiles_of_a_kv_heads_query_heads():
    q = jnp.zeros((2, 32 * 128), jnp.bfloat16)
    pool = jnp.zeros((1, 5, 64, 256), jnp.bfloat16)
    # off the TPU the gate is shut whatever the shapes
    assert not PA.use_paged_sparse(q, pool, 32, 2)
    orig = PA._on_one_tpu
    PA._on_one_tpu = lambda x: True
    try:
        assert PA.use_paged_sparse(q, pool, 32, 2)
        assert not PA.use_paged_sparse(q, pool, 16, 2)     # 8 heads a group
        assert not PA.use_paged_sparse(
            q, jnp.zeros((1, 5, 64, 256), jnp.float32), 32, 2)
    finally:
        PA._on_one_tpu = orig
