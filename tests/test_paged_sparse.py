"""The two kernels MiniCPM-SALA's decode step brings
(`ops/pallas/paged_attention.py`), through the Pallas TPU interpreter: the
block-sparse walk (`paged_sparse_attention`: a (slot, K/V head) pair's own
list of blocks) against plain attention over the tokens the lists name, and
the selection's scores read where the compressed keys lie
(`paged_select_scores`) against `block_scores` on the gathered keys, with
the gates that choose them and the pieces their walk goes by. The model
around them is tests/test_minicpm_sala.py's; what the interpreter cannot see
is tests/test_tpu_aot_compile.py's."""

import math

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.models import minicpm_sala as M
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import kv_cache as kvc


def _walk_case(tables, newest, *, bs, S, G=2, R=16, D=128, NB=None, seed=0,
               list_tokens=None, layer=1):
    """`paged_sparse_attention` (the Pallas TPU interpreter) over the pairs'
    lists `tables` `[S * G, W]` / `newest` `[S * G]`, each pair against
    plain attention over the tokens ITS list names, one K/V head's lanes
    at a time; a pair whose list starts with the null block gets zeros.
    -> the result `[S, G, R, D]` float32."""
    tables = np.asarray(tables, np.int32)
    newest = np.asarray(newest, np.int32)
    NB = NB or int(tables.max()) + 2
    rng = np.random.default_rng(seed)
    k_pool = jnp.asarray(rng.normal(size=(2, NB, bs, G * D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(2, NB, bs, G * D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(S, G * R * D)), jnp.bfloat16)
    got = PA.paged_sparse_attention(
        q, k_pool, v_pool, jnp.int32(layer), jnp.asarray(tables),
        jnp.asarray(newest), heads=G * R, kv_heads=G,
        list_tokens=list_tokens, interpret=pltpu.InterpretParams())
    got = np.asarray(got, np.float32).reshape(S, G, R, D)
    qf = np.asarray(q, np.float32).reshape(S, G, R, D)
    kf = np.asarray(k_pool, np.float32)[layer]
    vf = np.asarray(v_pool, np.float32)[layer]
    for pair in range(S * G):
        s, g = divmod(pair, G)
        if not tables[pair, 0]:
            assert not got[s, g].any(), pair
            continue
        n = newest[pair] + 1
        blocks = tables[pair, :-(-n // bs)]
        keys = kf[blocks].reshape(-1, G, D)[:n, g]
        vals = vf[blocks].reshape(-1, G, D)[:n, g]
        sc = qf[s, g] @ keys.T / math.sqrt(D)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ vals
        np.testing.assert_allclose(got[s, g], want, atol=3e-2, rtol=3e-2,
                                   err_msg=f"pair {pair}")
    return got


def _sparse_lists(rng, lead, trail, live=64, nb=400, window=None):
    """Two heads' lists of `live` entries that agree in their first `lead`
    and last `trail` entries and in nothing between: scattered picks, then
    the window's blocks (`window`: `(first id, count)` runs; one run of
    `trail` ids where None)."""
    if window is None:
        window = [(nb - trail, trail)] if trail else []
    tail = np.concatenate([np.arange(a, a + k) for a, k in window]) \
        if window else np.zeros((0,), np.int64)
    assert len(tail) == trail
    free = np.arange(1, nb - trail - 40)
    head = np.sort(rng.choice(free[::3], lead, replace=False))
    lists = []
    for g in range(2):
        # picks of a head's own: of the ids no other list holds
        own = np.sort(rng.choice(free[1 + g::3], live - lead - trail,
                                 replace=False))
        lists.append(np.concatenate([head, own, tail]))
    return lists


def _tables_of(lists, width):
    tables = np.zeros((len(lists), width), np.int32)
    for pair, ids in enumerate(lists):
        tables[pair, :len(ids)] = ids
    return tables


# (slot, K/V head) pairs' lists: name -> (bs) -> (lists a pair, newest a
# pair, list_tokens); 128 entries wide like the cell's
_WALK_CASES = {
    # the first cases this file had: a sparse pair's scattered blocks with
    # a partial newest one beside a pair that reads a run of all its width,
    # one token, an inactive slot: heads that END APART
    "lists_that_end_apart": lambda bs, rng: (
        [[7, 3, 30, 31, 32], np.arange(20, 32), [9], [2, 39, 1], [], []],
        [4 * bs + bs // 2, 12 * bs - 1, 0, 2 * bs + 3, 0, 0], None),
    # what a step of the cell hands over: entry 0 and the window's 33
    # blocks the same in both lists, one run; 32 where the position falls
    # at a block's end
    "shared_1_and_33": lambda bs, rng: (
        _sparse_lists(rng, 1, 33) + _sparse_lists(rng, 1, 32),
        [63 * bs + 5] * 2 + [64 * bs - 1] * 2, 64 * bs),
    "shared_nothing": lambda bs, rng: (
        _sparse_lists(rng, 0, 0) + _sparse_lists(rng, 0, 0, live=20),
        [63 * bs + bs // 2 + 1] * 2 + [19 * bs] * 2, 64 * bs),
    "shared_1_and_1": lambda bs, rng: (
        _sparse_lists(rng, 1, 1), [63 * bs + bs - 2] * 2, 64 * bs),
    "shared_5_and_0": lambda bs, rng: (
        _sparse_lists(rng, 5, 0), [63 * bs + 1] * 2, 64 * bs),
    # the window's run broken in two: a prompt's tail and its growth
    "window_in_two_runs": lambda bs, rng: (
        _sparse_lists(rng, 1, 33, window=[(300, 20), (350, 13)])
        + _sparse_lists(rng, 1, 33, window=[(380, 1), (310, 32)]),
        [63 * bs + 9] * 2 + [63 * bs] * 2, 64 * bs),
    # no two ids follow each other anywhere, shared or not
    "no_run_at_all": lambda bs, rng: (
        (lambda same, a, b: [np.concatenate([same[:1], a, same[1:]]),
                             np.concatenate([same[:1], b, same[1:]])])(
            np.arange(2, 70, 2), np.arange(101, 161, 2),
            np.arange(201, 261, 2)),
        [63 * bs + 3] * 2, 64 * bs),
    # rows that read their own table for both heads: at `dense_len`
    # exactly (all of the width) and under it; every entry is shared
    "dense_rows": lambda bs, rng: (
        [np.arange(10, 138)] * 2 + [np.r_[200:230, 150:171]] * 2,
        [128 * bs - 1] * 2 + [50 * bs + 7] * 2, 64 * bs),
    # one K/V head's list live and the other's null, either way round
    "one_head_null": lambda bs, rng: (
        [_sparse_lists(rng, 1, 33)[0], [], [],
         _sparse_lists(rng, 1, 33)[1]],
        [63 * bs + bs - 3, 0, 0, 62 * bs + 4], 64 * bs),
}


@pytest.mark.parametrize("case,bs", [
    (case, bs) for case in _WALK_CASES for bs in (16, 64)
    # elsewhere the block's size changes nothing
    if bs == 16 or case in ("lists_that_end_apart", "shared_1_and_33",
                            "dense_rows")])
def test_the_sparse_walk_reads_each_pairs_own_blocks(case, bs):
    """A slot's K/V heads together, each over the list of ITS blocks:
    lists that agree in a prefix and a suffix of several lengths (nothing,
    one entry, the cell's 1 and 33, everything: what agrees is fetched
    once for both heads), a window in two runs, lists without a run, rows
    that read their own table, a head that reads nothing beside one that
    does, lists that end apart; against plain attention over the tokens
    the lists name."""
    lists, newest, list_tokens = _WALK_CASES[case](
        bs, np.random.default_rng(len(case)))
    width = 12 if case == "lists_that_end_apart" else 128
    tables = _tables_of(lists, width)
    shared = np.asarray(PA.pair_lists(
        jnp.asarray(tables), jnp.asarray(newest, jnp.int32), 2, bs).shared)
    want = {"shared_1_and_33": [[1, 33], [1, 32]],
            "shared_nothing": [[0, 0], [0, 0]],
            "shared_1_and_1": [[1, 1]], "shared_5_and_0": [[5, 0]],
            "window_in_two_runs": [[1, 33], [1, 33]],
            "no_run_at_all": [[1, 33]],
            "dense_rows": [[128, 0], [51, 0]],
            "one_head_null": [[0, 0], [0, 0]]}.get(case)
    if want is not None:
        assert shared.tolist() == want
    _walk_case(tables, newest, bs=bs, S=len(lists) // 2,
               list_tokens=list_tokens, seed=bs)


@pytest.mark.parametrize("list_tokens,chunk", [(1024, 1024), (2048, 2048),
                                               (3000, 2048), (4096, 4096),
                                               (None, 4096)])
def test_the_sparse_walk_at_every_chunk_it_can_pick(list_tokens, chunk):
    """`sparse_chunk_tokens`: the longest of 1024 / 2048 / 4096 tokens that
    a sparse row's list (`list_tokens`; the table's width where none is
    named) fills; lists of 83 and of 64 blocks of 64 tokens are then 6 /
    3 / 2 chunks, the last one partly live, the shared entries across the
    cuts; noted for `status()["decode_attention"]`."""
    bs = 64
    assert PA.sparse_chunk_tokens(1024, list_tokens or 128 * bs) == chunk
    rng = np.random.default_rng(chunk)
    lists = _sparse_lists(rng, 3, 40, live=83) + _sparse_lists(rng, 1, 33)
    PA.GATE_COUNTS.clear()
    _walk_case(_tables_of(lists, 128), [82 * bs + 11] * 2 + [63 * bs + 60] * 2,
               bs=bs, S=2, list_tokens=list_tokens, seed=chunk)
    assert PA.WALK_CHUNKS["paged_sparse"] == chunk
    assert PA.gate_report() == {}
    PA.GATE_COUNTS["paged_sparse"] += 1
    assert PA.gate_report() == {"paged_sparse": 1,
                                "paged_sparse_chunk_tokens": chunk}
    PA.GATE_COUNTS.clear()


def test_a_slots_result_does_not_depend_on_its_neighbours():
    """`ServeModel`'s contract through the walk: slot 1's lists and query
    stay, the slots around it change (other lists, other lengths, an idle
    slot, other shared counts), and slot 1's result is the same BITS."""
    bs, S = 16, 3
    rng = np.random.default_rng(5)
    mine = _sparse_lists(rng, 1, 33)
    batches = [
        (_sparse_lists(rng, 1, 32) + mine + _sparse_lists(rng, 0, 0),
         [64 * bs - 1] * 2 + [63 * bs + 5] * 2 + [63 * bs + 2] * 2),
        ([[], []] + mine + [np.arange(100, 228)] * 2,
         [0, 0] + [63 * bs + 5] * 2 + [128 * bs - 1] * 2),
        (_sparse_lists(rng, 9, 2, live=30) + mine + [[5], []],
         [29 * bs + 3] * 2 + [63 * bs + 5] * 2 + [0, 0])]
    got = [_walk_case(_tables_of(lists, 128), newest, bs=bs, S=S, NB=420,
                      list_tokens=64 * bs, seed=11)[1]
           for lists, newest in batches]
    assert (got[0] == got[1]).all() and (got[0] == got[2]).all()


def test_the_lists_runs_and_shared_entries_are_counted_by_compares():
    """`pair_lists` against a count by hand: of every entry of a slot's
    first list how many ids from it on follow each other; of a slot's live
    entries how many from
    the first on and how many up to the last are the same in both lists
    (none at the ends of lists that end apart, none for a null list; a
    list that is the same all through counts once, from the front)."""
    bs = 16
    lists = [[5, 6, 7, 20, 40, 41, 42, 43], [5, 6, 9, 21, 40, 41, 42, 43],
             [5, 6, 7, 8], [5, 6, 7, 8],
             [5, 6, 7, 8, 9], [5, 6, 7, 8],
             [], [3, 4],
             [9, 8, 7, 30], [9, 1, 2, 30]]
    newest = [8 * bs - 1, 7 * bs + 2, 3 * bs, 3 * bs + 5, 4 * bs, 4 * bs - 1,
              0, bs, 4 * bs - 1, 4 * bs - 1]
    got = PA.pair_lists(jnp.asarray(_tables_of(lists, 10)),
                        jnp.asarray(newest, jnp.int32), 2, bs)
    assert np.asarray(got.shared).tolist() == [
        [2, 4], [4, 0], [4, 0], [0, 0], [1, 1]]
    runs = np.asarray(got.runs)         # of a slot's first list
    assert runs.shape == (5, 10)
    assert runs[0].tolist() == [3, 2, 1, 1, 4, 3, 2, 1, 1, 1]
    assert runs[4].tolist() == [1, 1, 1, 1, 1, 1, 1, 1, 1, 1]
    assert runs[2, :5].tolist() == [5, 4, 3, 2, 1]
    assert (np.asarray(got.ids) == _tables_of(lists, 10)).all()


# (the cache's token bytes in both pools, its table's tokens, its block):
# the other serve cells' and this cell's own allocator's; what
# `chunk_tokens` / `blocks_per_chunk` gave before the sparse walk had a
# number of its own, which the allocator's `per_chunk` and the three other
# walks still go by
@pytest.mark.parametrize("cell,token_bytes,table,bs,chunk,per_chunk", [
    ("gpt2_large", 2 * 1280 * 2, 1024, 16, 256, 16),
    ("olmoe_1b_7b", 2 * 2048 * 2, 1024, 16, 256, 16),
    ("nemotron3_nano.reason_closed", 2 * 256 * 2, 2560, 16, 512, 32),
    ("nemotron3_nano.doc_sessions", 2 * 256 * 2, 9216, 16, 512, 32),
    ("jamba2_3b", 2 * 128 * 2, 1024, 16, 512, 32),
    ("joyai_llm_flash", (512 + 128) * 2, 4608, 16, 512, 32),
    ("xing4_29b_a4b", (512 + 128) * 2, 20480, 16, 1024, 64),
    ("longcat_flash_chat", (512 + 128) * 2, 1024, 16, 512, 32),
    ("minicpm_sala", 2 * 256 * 2, 49152, 64, 1024, 16)])
def test_the_other_walks_chunks_are_what_they_were(cell, token_bytes, table,
                                                   bs, chunk, per_chunk):
    assert PA.chunk_tokens(token_bytes, table) == chunk
    assert PA.blocks_per_chunk(bs, token_bytes, table) == per_chunk


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
