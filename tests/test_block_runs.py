"""The block allocator hands out RUNS (serving/kv_cache.py): a new table is
one ascending run of consecutive ids wherever a free extent holds it, a
table grows by the block after its last one when that is free, nothing is
reserved ahead of need, and `run_chunks` / `status()["kv"]
["run_chunk_share"]` count the chunks the paged decode kernels read with
one copy a pool (ops/pallas/paged_attention.py; the kernels' side of it is
tests/test_paged_attention.py's). Which blocks hold a sequence never
shows in its tokens.
"""

import time

import jax
import numpy as np
import pytest

from paddle_tpu.models import gpt
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import DecodeConfig, DecodeEngine, kv_cache
from paddle_tpu.serving.kv_cache import (BlockAllocator, KVCacheConfig,
                                         NoBlocksError, run_chunks)


def _allocator(num_blocks=65, widths=(1280, 1280), max_len=256):
    return BlockAllocator(KVCacheConfig(
        layers=1, widths=widths, max_len=max_len, block_size=16,
        num_blocks=num_blocks))


def _free_ids(al):
    return sorted(set(range(1, al.cfg.num_blocks)) - set(al._owned))


def _extents(ids):
    """[(start, length)] of a sorted list of ids."""
    out = []
    for b in ids:
        if out and out[-1][0] + out[-1][1] == b:
            out[-1][1] += 1
        else:
            out.append([b, 1])
    return [tuple(e) for e in out]


def _pieces(blocks):
    return len(_extents(blocks)) if blocks else 0


def test_a_fresh_pool_hands_out_1_2_3():
    al = _allocator()
    assert al.alloc(3) == [1, 2, 3]
    assert al.alloc(1) == [4]
    assert al.alloc(0) == []
    assert al.alloc(5) == [5, 6, 7, 8, 9]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_after_a_churn_a_new_table_is_one_run_or_the_fewest_pieces(seed):
    """Interleaved allocations, growth and retirements; then every size:
    one ascending run (the lowest extent that holds it) whenever some
    free extent does, else the fewest pieces, each ascending, in address
    order, the largest extents first."""
    rng = np.random.default_rng(seed)
    al = _allocator(num_blocks=129)
    held = []
    for _ in range(400):
        if held and (rng.random() < 0.45 or not al.can_alloc(12)):
            al.free(held.pop(int(rng.integers(len(held)))))
        elif held and rng.random() < 0.4:
            if al.can_alloc(1):
                al.grow(held[int(rng.integers(len(held)))])
        else:
            held.append(al.alloc(int(rng.integers(1, 13))))
        owned = sorted(b for t in held for b in t)
        assert owned == sorted(al._owned) and len(set(owned)) == len(owned)
        assert al.free_blocks() + al.used_blocks() == 128
        assert _free_ids(al) == [b for s in al._free._starts
                                 for b in range(s, al._free._end[s])]
    tried = 0
    for n in range(1, al.free_blocks() + 1):
        free = _extents(_free_ids(al))
        got = al.alloc(n)
        tried += 1
        fits = [s for s, k in free if k >= n]
        if fits:
            assert got == list(range(fits[0], fits[0] + n))
        else:
            by_size = sorted((k for _, k in free), reverse=True)
            fewest = next(i + 1 for i in range(len(by_size))
                          if sum(by_size[:i + 1]) >= n)
            assert _pieces(got) == fewest
            assert got == sorted(got) and len(got) == n
        al.free(got)
    assert tried


def test_shortage_is_all_or_nothing():
    al = _allocator(num_blocks=9)
    a, b = al.alloc(3), al.alloc(3)
    before = (_free_ids(al), al.stats()["run_chunk_share"])
    with pytest.raises(NoBlocksError):
        al.alloc(3)
    assert (_free_ids(al), al.stats()["run_chunk_share"]) == before
    assert al.can_alloc(2) and not al.can_alloc(3)
    al.alloc(2)
    with pytest.raises(NoBlocksError):
        al.grow(a)
    assert a == [1, 2, 3] and b == [4, 5, 6]
    with pytest.raises(ValueError):
        al.alloc(-1)


def test_growth_takes_the_block_after_the_last_one_when_it_is_free():
    al = _allocator(num_blocks=65)
    a = al.alloc(3)             # 1 2 3
    al.grow(a)                  # 4 is free: the run goes on
    assert a == [1, 2, 3, 4]
    b = al.alloc(2)             # 5 6: a's next block is taken
    al.grow(a)
    # a new run from the middle of the largest extent (7..64), with room
    # on both sides, and it goes on from there
    assert a[-1] == (7 + 65) // 2 and al.used_blocks() == 7
    al.grow(a)
    assert a[-2:] == [36, 37]
    # nothing was reserved: every other block is still anyone's
    assert al.free_blocks() == 64 - 8 and al.can_alloc(56)
    al.free(b)
    c = al.alloc(2)
    assert c == [5, 6]          # the lowest extent that holds two
    al.grow(c)
    assert c == [5, 6, 7]


class _LowestFreeBlock(kv_cache._FreeExtents):
    """The simpler fallback of a growth that misses: the lowest free
    block, `take(1)`."""

    def take_after(self, last):
        if last + 1 in self._end:
            return self._carve(last + 1, last + 1, 1)[0]
        return self.take(1)[0]


def _steady_run_chunk_share(extents, slots, table_blocks, prompt, output,
                            widths, steps, seed=3):
    """Mean `run_chunk_share` of a closed loop over the allocator, past its
    first third: `slots` clients, each sending its next request when the
    last ends (a prompt of `prompt` tokens log-uniform in one `alloc`,
    `output` tokens uniform, a block grown when a token needs it), as the
    benchmark's closed serve cells run it."""
    rng = np.random.default_rng(seed)
    al = BlockAllocator(KVCacheConfig(
        layers=1, widths=widths, max_len=16 * table_blocks, block_size=16,
        num_blocks=slots * table_blocks + 1))
    al._free = extents(1, al.cfg.num_blocks)

    def admit():
        tokens = int(np.exp(rng.uniform(*np.log(prompt))))
        return [al.alloc(-(-tokens // 16)), tokens,
                tokens + int(rng.integers(output[0], output[1] + 1))]

    seqs = [admit() for _ in range(slots)]
    shares = []
    for step in range(steps):
        for i, seq in enumerate(seqs):
            if seq[1] >= seq[2]:
                al.free(seq[0])
                seqs[i] = seq = admit()
            while seq[1] // 16 >= len(seq[0]):
                al.grow(seq[0])
            seq[1] += 1
        if step > steps // 3 and step % 50 == 0:
            shares.append(al.stats()["run_chunk_share"])
    return float(np.mean(shares))


@pytest.mark.parametrize("slots,table_blocks,prompt,output,widths,gain", [
    (32, 288, (1024, 4096), (128, 512), (512, 128), 0.02),
    (64, 160, (256, 1024), (512, 1536), (256, 256), 0.15),
], ids=["rag_closed", "reason_closed"])
def test_a_growth_that_misses_starts_a_run_in_the_middle_of_the_largest_extent(
        slots, table_blocks, prompt, output, widths, gain):
    """Why `grow` falls back to the middle of the largest free extent and
    not to the lowest free block: over the closed cells' traffic more of
    the tables' chunks stay runs, most where the tables are mostly growth.
    The lowest free block is where the sequence before it grows next and
    where the next admission lands, so a run started there is cut short;
    the middle of the largest extent has room on both sides. On the chip
    (PERF.md section 6, PR 40) `run_chunk_share` read 0.905 against 0.858
    in `joyai_llm_flash.rag_closed` and 0.71 against 0.355 in
    `nemotron3_nano.reason_closed`, whose `gqa_attention_roofline` read
    53.0% against 41.3%."""
    shares = {ext: _steady_run_chunk_share(
        ext, slots, table_blocks, prompt, output, widths, steps=3000)
        for ext in (kv_cache._FreeExtents, _LowestFreeBlock)}
    middle, lowest = shares[kv_cache._FreeExtents], shares[_LowestFreeBlock]
    assert middle > lowest + gain, shares
    assert middle > 0.6, shares


def test_double_free_and_foreign_ids_still_raise():
    al = _allocator()
    got = al.alloc(4)
    al.free(got[:2])
    with pytest.raises(ValueError, match="double free"):
        al.free(got[:1])
    with pytest.raises(ValueError, match="null block"):
        al.free([0])
    with pytest.raises(ValueError, match="not allocated"):
        al.free([40])
    assert al.used_blocks() == 2
    al.free(got[2:])
    assert al.alloc(64) == list(range(1, 65))       # one extent again


def _brute(blocks, per_chunk):
    runs = chunks = 0
    for at in range(0, len(blocks), per_chunk):
        chunk = blocks[at:at + per_chunk]
        chunks += 1
        runs += all(b == chunk[0] + j for j, b in enumerate(chunk))
    return runs, chunks


@pytest.mark.parametrize("per_chunk", [1, 4, 16])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_run_chunks_agrees_with_a_brute_force_count(seed, per_chunk):
    rng = np.random.default_rng(seed)
    assert run_chunks([], per_chunk) == (0, 0)
    assert run_chunks([7], per_chunk) == (1, 1)
    for _ in range(60):
        # runs of random lengths from random places, some touching
        blocks = []
        while len(blocks) < int(rng.integers(1, 70)):
            start = int(rng.integers(1, 500))
            blocks += list(range(start, start + int(rng.integers(1, 25))))
            if rng.random() < 0.3:
                blocks += list(range(blocks[-1] + 1, blocks[-1] + 4))
        assert run_chunks(blocks, per_chunk) == _brute(blocks, per_chunk), \
            blocks


@pytest.mark.parametrize("widths,max_len,per_chunk", [
    ((1280, 1280), 256, 16), ((512, 128), 256, 32),
    ((512, 128), 20480, PA._LONG_CHUNK // 16),
    ((1280, 1280), 20480, 16)])
def test_the_allocators_count_follows_its_tables(widths, max_len, per_chunk):
    """`run_chunk_share` against a recount of the live tables, through
    allocations, growth (adjacent and not) and retirements; the chunk is
    the kernels' for the cache's width and the table's (multi-head K and
    V of 1280 lanes: 256 tokens under any table; a latent cache: 512, and
    `_LONG_CHUNK` under a table of 20480 tokens)."""
    rng = np.random.default_rng(5)
    al = _allocator(num_blocks=513, widths=widths, max_len=max_len)
    assert al.stats()["run_chunk_share"] is None
    assert al.per_chunk == per_chunk
    assert al.stats()["walk_chunk_tokens"] == 16 * per_chunk
    held = []
    for step in range(600):
        roll = rng.random()
        if held and (roll < 0.1 or not al.can_alloc(40)):
            al.free(held.pop(int(rng.integers(len(held)))))
        elif held and roll < 0.8:
            al.grow(held[int(rng.integers(len(held)))])
        else:
            held.append(al.alloc(int(rng.integers(1, 40))))
        counted = np.sum([run_chunks(t, per_chunk) for t in held], axis=0) \
            if held else (0, 0)
        assert (al._runs, al._chunks) == tuple(counted)
        share = al.stats()["run_chunk_share"]
        assert share == (round(counted[0] / counted[1], 4) if held else None)
    assert 0 < share <= 1


def _runs_from(chunk, at):
    """How many ids of `chunk` follow each other from entry `at` on."""
    n = at < len(chunk)
    while at + n < len(chunk) and chunk[at + n] == chunk[at + n - 1] + 1:
        n += 1
    return int(n)


@pytest.mark.parametrize("table_blocks", [288, 576, 1280],
                         ids=["rag_closed", "doc_sessions",
                              "ctx12k_sessions"])
def test_the_kernels_count_of_runs_is_the_hosts(table_blocks):
    """ONE definition of a chunk: what `with_runs` counts for the walk
    (`Tables.runs`), what `run_chunks` counts on the host and what the
    allocator keeps (`per_chunk`, `run_chunk_share`) agree chunk for
    chunk over tables as the allocator builds them, at 32 blocks a chunk
    under a table of 4608 or 9216 tokens and at `_LONG_CHUNK` tokens
    under one of 20480; a long chunk's second count is the run after its
    first break."""
    import jax.numpy as jnp

    rng = np.random.default_rng(table_blocks)
    slots = 6
    al = BlockAllocator(KVCacheConfig(
        layers=1, widths=(512, 128), max_len=16 * table_blocks,
        block_size=16, num_blocks=slots * table_blocks + 1))
    long = 16 * table_blocks >= PA._LONG_TABLE
    per_chunk = al.per_chunk
    assert per_chunk == (PA._LONG_CHUNK // 16 if long else 32)
    held = [al.alloc(int(rng.integers(1, table_blocks // 2)))
            for _ in range(slots)]
    for _ in range(40 * slots):     # growth, and a retirement now and then
        i = int(rng.integers(slots))
        if rng.random() < 0.05:
            al.free(held[i])
            held[i] = al.alloc(int(rng.integers(1, table_blocks // 2)))
        for _ in range(int(rng.integers(1, 8))):
            if len(held[i]) < table_blocks:
                al.grow(held[i])
    ids = np.zeros((slots, table_blocks), np.int32)
    for s, t in enumerate(held):
        ids[s, :len(t)] = t
    pools = [jnp.zeros((1, 2, 16, w), jnp.bfloat16) for w in (512, 128)]
    runs = np.asarray(PA.with_runs(jnp.asarray(ids), *pools).runs)
    chunks = -(-table_blocks // per_chunk)
    assert runs.shape == (slots, 2 * chunks if long else chunks)
    whole = total = 0
    for s, t in enumerate(held):
        for c in range(-(-len(t) // per_chunk)):
            chunk = t[c * per_chunk:(c + 1) * per_chunk]
            lead = min(int(runs[s, c]), len(chunk))
            assert lead == _runs_from(chunk, 0)
            assert (lead == len(chunk)) == (run_chunks(chunk, per_chunk)
                                            == (1, 1))
            if long and lead < len(chunk):
                assert min(int(runs[s, chunks + c]), len(chunk) - lead) \
                    == _runs_from(chunk, lead)
            whole += lead == len(chunk)
            total += 1
        assert run_chunks(t, per_chunk)[1] == -(-len(t) // per_chunk)
    assert 0 < whole < total        # the churn left breaks, and runs
    assert (whole, total) == tuple(np.sum(
        [run_chunks(t, per_chunk) for t in held], axis=0))
    assert al.stats()["run_chunk_share"] == round(whole / total, 4)


# -- in the engine -----------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"
    params, _ = gpt.init(jax.random.key(0), cfg)
    return params, cfg


def _engine(model, **kw):
    params, cfg = model
    base = dict(block_size=8, num_blocks=24, decode_slots=(2,),
                prefill_buckets=(8, 16), precision="f32", max_len=64)
    base.update(kw)
    return DecodeEngine(params, cfg, DecodeConfig(**base))


def _wait(cond, timeout=120.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, "timed out"
        time.sleep(0.005)


def _kv_with(eng, active):
    """`status()["kv"]` once `active` sequences are resident, read with
    the scheduler held at its next look at the queue."""
    _wait(lambda: eng.status()["active"] == active)
    with eng._cv:
        st = eng.status()
        assert st["active"] == active
        return st["kv"]


def test_status_reports_the_share_through_admission_growth_and_retirement(
        model):
    """block_size 8 on a kernel chunk of 512 tokens (a narrow cache): 64
    blocks a chunk, so every table here is one chunk, a run or not. The
    long streams are long so that a resident is still there when the test
    looks."""
    eng = _engine(model, max_len=256, num_blocks=48)
    try:
        assert eng.status()["kv"]["run_chunk_share"] is None
        # two residents, admitted in one turn: 1..2 and 3..4; the first
        # cannot grow in place, the second can
        with eng._cv:
            a = eng.submit(list(range(1, 13)), max_new_tokens=240)
            b = eng.submit(list(range(3, 17)), max_new_tokens=7)
        assert len(b.result(timeout_s=120)) == 7
        # b retired at 14 + 7 = 21 positions, three blocks; a's table
        # left its run at its third block
        kv = _kv_with(eng, 1)
        assert kv["run_chunk_share"] == 0.0 and kv["blocks_used"] >= 3
        assert len(a.result(timeout_s=120)) == 240
        kv = _kv_with(eng, 0)
        assert kv["run_chunk_share"] is None and kv["blocks_used"] == 0
        # alone in the pool a table is a run from admission to its end
        c = eng.submit(list(range(2, 11)), max_new_tokens=240)
        kv = _kv_with(eng, 1)
        assert kv["run_chunk_share"] == 1.0
        assert len(c.result(timeout_s=120)) == 240
        assert eng.status()["kv"]["blocks_free"] == 47
    finally:
        eng.stop()


class _LifoAllocator(BlockAllocator):
    """The allocator's order before runs: a LIFO list, so that a table
    after a retirement is descending and scattered."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self._lifo = list(range(cfg.num_blocks - 1, 0, -1))

    def free_blocks(self):
        return len(self._lifo)

    def can_alloc(self, n):
        return n <= len(self._lifo)

    def alloc(self, n):
        if n > len(self._lifo):
            raise NoBlocksError(f"need {n}")
        out = [self._lifo.pop() for _ in range(n)]
        self._owned.update((b, True) for b in out)
        return out

    def grow(self, table):
        table.extend(self.alloc(1))

    def free(self, blocks):
        for b in blocks:
            del self._owned[b]
            self._lifo.append(b)


def test_tokens_do_not_depend_on_which_blocks_hold_them(model):
    """A closed loop of short requests, two at a time over a small pool,
    through several retirements: the same streams under the run allocator
    and under the LIFO order it replaced, whose tables differ."""
    rng = np.random.default_rng(2)
    jobs = [(list(map(int, rng.integers(1, 90, size=int(n)))), int(m))
            for n, m in zip(rng.integers(3, 16, size=10),
                            rng.integers(4, 20, size=10))]
    streams, tables = [], []
    for lifo in (False, True):
        eng = _engine(model)
        if lifo:
            eng._alloc = _LifoAllocator(eng.kv_cfg)
        seen = []
        admitted = eng._prefill_admitted

        def spy(req, bucket, seen=seen, admitted=admitted):
            out = admitted(req, bucket)
            seen.append(list(req.blocks))
            return out

        eng._prefill_admitted = spy
        try:
            out, pending = [None] * len(jobs), {}
            todo = list(enumerate(jobs))
            while todo or pending:
                while todo and len(pending) < 2:
                    i, (prompt, n) = todo.pop(0)
                    pending[i] = eng.submit(prompt, max_new_tokens=n)
                i = min(pending)
                out[i] = pending.pop(i).result(timeout_s=120)
            assert eng.status()["kv"]["blocks_used"] == 0
        finally:
            eng.stop()
        streams.append(out)
        tables.append(seen)
    assert streams[0] == streams[1]
    assert all(len(s) == n for s, (_, n) in zip(streams[0], jobs))
    # the orders did differ, and the new one's tables are runs
    assert tables[0] != tables[1]
    assert all(run_chunks(t, 64) == (1, 1) for t in tables[0])
    assert any(run_chunks(t, 64) == (0, 1) for t in tables[1])


# -- a second kind: the window kind's rings -----------------------------------


def test_a_ring_repeats_over_the_table_and_a_short_one_does_not():
    assert kv_cache.ring_blocks(2048, 1024, 16) == 193
    assert kv_cache.window_table([5, 6, 7], 4, 10).tolist() \
        == [5, 6, 7, 0, 0, 0, 0, 0, 0, 0]
    assert kv_cache.window_table([5, 6, 7, 9], 4, 10).tolist() \
        == [5, 6, 7, 9, 5, 6, 7, 9, 5, 6]
    with pytest.raises(ValueError):
        kv_cache.window_table([1, 2, 3], 2, 10)


@pytest.fixture(scope="module")
def windowed():
    from paddle_tpu.models import afmoe

    cfg = afmoe.AfmoeConfig.tiny()      # a window of 32, slices of 16
    cfg.dtype = "float32"
    params, _ = afmoe.init(jax.random.key(0), cfg)
    return params, cfg


def _windowed_engine(windowed, **kw):
    return _engine(windowed, num_blocks=65, prefill_buckets=(16, 64),
                   max_len=160, **kw)


def test_a_window_kind_sequence_holds_a_ring_at_most_and_its_length_below_it(
        windowed):
    """Two kinds of pool in the engine: at ANY length a sequence holds at
    most `R` = (32 + 16) / 8 + 1 = 7 window-kind blocks while its global
    blocks follow its length; one shorter than the window holds its own
    length; release returns every block of both kinds."""
    eng = _windowed_engine(windowed)
    try:
        ring = eng.status()["kv"]["kinds"]["window"]["ring_blocks"]
        assert ring == 7
        seen = []
        long = eng.submit(list(range(1, 51)), max_new_tokens=90)
        short = eng.submit([7, 8, 9], max_new_tokens=12)
        while eng.status()["active"] or not seen:
            with eng._cv:
                for r in list(eng._active):
                    seen.append((r.prompt_len0, r.pos, len(r.blocks),
                                 len(r.wblocks)))
            time.sleep(0.002)
        assert len(long.result(timeout_s=120)) == 90
        assert len(short.result(timeout_s=120)) == 12
        assert max(pos for n, pos, _, _ in seen if n == 50) > 100
        for n, pos, blocks, wblocks in seen:
            assert wblocks <= ring
            assert blocks >= -(-pos // 8)
            # under the ring a sequence holds its length (a step ahead)
            assert wblocks == min(blocks, ring), (n, pos, blocks, wblocks)
        kinds = eng.status()["kv"]["kinds"]
        assert kinds["global"]["blocks_used"] == 0
        assert kinds["window"]["blocks_used"] == 0
        assert kinds["window"]["blocks_free"] == 2 * ring
    finally:
        eng.stop()


def test_admission_waits_while_either_kind_is_short(windowed):
    """`can_alloc` is asked of BOTH kinds: with the window kind's pool cut
    to one ring, a second long prompt waits for the first to finish though
    the global kind has room for both."""
    eng = _windowed_engine(windowed)
    try:
        eng._walloc = BlockAllocator(KVCacheConfig(
            layers=3, widths=eng._wkv_cfg.entry_widths, max_len=160,
            block_size=8, num_blocks=7 + 1, dtype="float32"))
        first = eng.submit(list(range(1, 61)), max_new_tokens=40)
        second = eng.submit(list(range(2, 62)), max_new_tokens=5)
        kv = _kv_with(eng, 1)
        assert eng.status()["queue_depth"] == 1
        assert kv["kinds"]["window"]["blocks_free"] == 0
        assert kv["kinds"]["global"]["blocks_free"] > 8
        assert len(first.result(timeout_s=120)) == 40
        assert len(second.result(timeout_s=120)) == 5
        assert eng.status()["kv"]["kinds"]["window"]["blocks_used"] == 0
    finally:
        eng.stop()
