"""MiniCPM-SALA (`paddle_tpu/models/minicpm_sala.py`) on the CPU at a tiny
size. What every served family must do is `tests/serve_contract.py`'s, bound
here with a `dense_len` so small that the contract's sequence crosses from
the dense to the sparse read while it decodes (a prompt walked in slices,
then decode steps through the paged K/V, the compressed-key entry and the
state rows, against the plain reference's full forward pass); what is this
model's own follows it: the selection against the reference's brute-force
one; the chunked lightning scan against the token recurrence, and the row
update against both; the third cache entry, written at every `stride`-th
token by prefill and decode alike and freed with its blocks. The
block-sparse walk's and the selection's kernels are
tests/test_paged_sparse.py's."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.reference import minicpm_sala_ref as ref
from paddle_tpu.models import minicpm_sala as M
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import ssm_update as SU
from paddle_tpu.serving import kv_cache as kvc
from serve_contract import (BS, Family, ServeContract, boot, seeded,
                            served_alone)


@functools.cache
def _tiny():
    cfg = M.MiniCPMSALAConfig.tiny()
    return cfg, seeded(M, cfg)


def _logits_of_whole_row_blocks(params, model, ids):
    """The reference walks whole blocks of 128 query rows: a causal model
    keeps the padding out of every row that is read."""
    full = np.zeros((-(-len(ids) // 128) * 128,), np.int32)
    full[:len(ids)] = ids
    return ref.logits_rows(params, model, jnp.asarray(full), 0, len(ids))


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return tuple(rng.integers(0, 512, size=n).tolist() for n in lengths)


FAMILY = Family(
    module=M, tiny=_tiny, ref=ref, logits=_logits_of_whole_row_blocks,
    tol=1e-4, tol_why="float32 throughout: rounding in another order of "
                      "summation, of logits whose deviation is 1",
    # each control of the cell's tolerance is a different model: the
    # reference with the switch set moves the logits of a sequence that is
    # over `dense_len`
    faults=(("dense_walk", {"dense_walk": True}, 1e-2),
            ("sparse_rope", {"sparse_rope": True}, 1e-2),
            ("lin_rope", {"lin_rope": False}, 1e-2),
            ("decay_one", {"decay_one": True}, 1e-2),
            ("bf16_state_layer", {"bf16_state_layer": 1}, 1e-4)),
    # a prompt of 40 tokens in a bucket of 64 (two slices of 32, the second
    # half padding), then 60 decode steps: positions 40..99, over
    # `dense_len` 48 from the ninth step on
    prompts=(40,), total=100, bucket=64, max_len=192,
    engine=dict(num_blocks=4 * 24 + 1, prefill_buckets=(32, 64, 96),
                max_len=192),
    engine_prompts=_prompts(0, (40, 70, 33)), max_new=40,
    # 21 blocks, 168 tokens: both are admitted (16 blocks) and cannot both
    # grow to their 13; a replay's prompt is prompt + generated
    tight=(dict(num_blocks=22, prefill_buckets=(32, 64, 96, 128)),
           _prompts(7, (60, 62)), 40),
    # a short sequence alone: a dense row or none, nothing selected
    counters={"sparse_rows": (0, 0), "dense_tokens": (0, 16),
              "blocks_selected": (0, 0), "shared_entries": (0, 2),
              "kc_entries": (0, 0)},
    scopes=frozenset({"ssm_in", "scan", "ssm_out", "kc_write"}),
    stepping=frozenset({"state_read", "state_write", "select"}))


class TestContract(ServeContract):
    family = FAMILY

    def test_a_steps_counters_follow_the_crossing(self, programs):
        """A dense row until 48 tokens, then a sparse one that reads topk =
        4 blocks and scores n // 4 - 1 compressed keys."""
        sm, n = programs.sm, FAMILY.prompts[0]
        facts = [sm.step_facts(jax.device_get(
            programs.served("whole", n, upto).stats))
            for upto in (n + 1, n + 8, n + 9, FAMILY.total)]
        assert facts[0]["sparse_rows"] == 0 == facts[1]["sparse_rows"]
        assert facts[0]["dense_tokens"] == 41
        for f in facts[2:]:
            assert f["sparse_rows"] == 1 and f["blocks_selected"] == 4.0 \
                and f["dense_tokens"] == 0
        assert facts[-1]["kc_entries"] == FAMILY.total // 4 - 1
        # what a slot's K/V heads read whatever they score: a dense row
        # its whole list (41 and 48 tokens: 3 blocks of 16), a sparse row
        # the first block and those of the newest 32 tokens, 3 of them or,
        # at a block's end (96 tokens), 2: all 4 taken, or 3 of the 4
        assert [f["shared_entries"] for f in facts] == [3, 3, 4, 4]
        at_a_blocks_end = sm.step_facts(jax.device_get(
            programs.served("whole", n, 96).stats))
        assert at_a_blocks_end["shared_entries"] == 3 \
            and at_a_blocks_end["blocks_selected"] == 4.0

    def test_prefill_and_decode_write_the_same_compressed_keys(self,
                                                               programs):
        """The third pool after a prompt of 64 tokens equals the pool after
        a prompt of 32 and 32 decode steps over the same tokens, entry by
        entry: the mean of the 8 newest cached keys every 4th token, in the
        blocks of the sequence's table."""
        cfg = programs.cfg
        first = programs.blocks[:8]
        keys = np.asarray(programs.served("whole", 64, 64).cache.k)
        by_prefill, by_decode = (
            np.asarray(programs.served("whole", n, 64).cache.state[-1])
            for n in (64, 32))
        per = BS // cfg.kernel_stride
        W = cfg.kv_heads * cfg.head_dim
        for layer in range(2):
            cached = keys[layer, first].reshape(64, W)
            for e in range(1, 16):
                row, at = first[e // per], (e % per) * W
                want = cached[4 * (e - 1):4 * (e + 1)].mean(0)
                np.testing.assert_allclose(
                    by_prefill[layer, row, at:at + W], want, atol=1e-6)
                np.testing.assert_allclose(
                    by_decode[layer, row, at:at + W], want, atol=1e-5)
        # nothing outside the sequence's blocks but the null block was
        # written
        others = [b for b in range(1, by_prefill.shape[1])
                  if b not in first]
        assert not by_prefill[:, others].any()

    def test_the_engine_reports_the_third_entry(self, engine):
        served_alone(engine, [FAMILY.engine_prompts[0]], 3)
        status = engine.status()
        assert status["kv"]["rated_entries"] == [
            {"width": 32, "stride": 4, "bytes_per_token_layer": 32.0}]
        assert status["kv"]["bytes_per_token_layer"] == (64 + 8) * 4
        assert status["kv"]["pool_bytes"] == engine.kv_cfg.pool_bytes() \
            == 2 * 97 * (2 * 8 * 32 + 2 * 32) * 4
        assert status["state"]["rows"] == 4
        assert status["state"]["bytes"] == 2 * 5 * 4 * 16 * 16 * 4
        # off the chip the compressed keys are gathered, never walked
        assert set(status["decode_attention"]) == {"gather",
                                                   "select_gather"}
        assert status["state"]["update"].get("xla", 0) >= 2
        assert status["kv"]["blocks_used"] == 0 \
            and status["state"]["used"] == 0

    def test_boot_refuses_what_the_third_entry_cannot_serve(self, programs):
        for bad in (dict(prefix_cache=True, prefill_chunk=16),
                    dict(prefill_chunk=16)):
            with pytest.raises(ValueError, match="cannot be served with"):
                boot(FAMILY, programs.params, programs.cfg, **bad)


def test_the_pattern_and_the_pools_are_the_models():
    cfg, params = _tiny()
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
