"""Decode attention over the paged KV pool, read through the block table.

One query row a slot against that slot's LIVE blocks of one layer of the
pools `[L, NB, BS, heads*head_dim]` (serving/kv_cache.py), which stay where
they lie in HBM: the kernel takes the whole pools, the layer index, the
block tables and the positions, and fetches block by block what the tables
name. The gather path it replaces in `models/decoder.decode_step` built
`[S, MB*BS, H*D]` for K and for V in every layer whatever was live, viewed
that as heads (a second, padded copy at 64-wide heads) and attended on the
VPU (PERF.md section 6, PR 28).

Two routes, one gate, as in `attention.py` and `grouped_matmul.py`: on a
TPU, at a pool whose tiles the kernel can address, the kernel; everywhere
else `decode_step` keeps the gather. The pick is final.

How it stays lane-dense. A token's heads lie side by side in the lanes and
are never viewed as `[heads, head_dim]`. The slot's query row `[1, H*D]` is
spread over `M >= heads` sublanes block-diagonally (`Q[h, h*D:(h+1)*D] =
q[h]`, zero elsewhere), so for a chunk of T tokens

    scores [M, T]   = Q [M, H*D] . K [T, H*D]^T        (MXU, f32)
    acc    [M, H*D] += P [M, T] . V [T, H*D]           (MXU, f32)

with an online softmax per head (row) over the chunks; row h of `acc` holds
head h's context in its own D lanes and other heads' in the rest, which the
block-diagonal mask drops once a slot, when the rows are summed to
`[1, H*D]`. 64-wide and 128-wide heads differ in D alone.

Grouped-query attention (fewer K/V heads than query heads: `_gqa_kernel`)
is the same walk with the `heads / kv_heads` query heads of a K/V head as
ONE query block: query head h sits in row h, in the lanes of ITS K/V head
(`Q[h, g*D:(g+1)*D] = q[h]`, g = h // group), so one `Q . K^T` scores all
query heads against the K/V rows as they lie, and row h's context is its
K/V head's D lanes of `acc`. A token is read once for all of its group.

How it reads only what is live. The grid walks the slots in order (one
TensorCore; the steps depend on each other through the buffers). A slot
with position p owns `p // BS + 1` blocks; a slot whose table starts with
the null block is inactive and reads nothing. Blocks are fetched a chunk
(`chunk_tokens // BS`) at a time into one of two VMEM buffers; while a
chunk is consumed the next is in flight, and the last chunk of a slot
overlaps the first of the next slot. How a slot's context is chunked
depends on its own length and on static shapes alone (the cache's token
bytes, the table's width: `chunk_tokens`), so a row's result does not
depend on what shares the batch (`ServeModel`'s contract). Rows of a buffer
past the live blocks hold what an earlier chunk left there: their scores
are masked, and their weights are exactly zero against V rows that are
finite (the buffers start zeroed; the pool's garbage is finite by the same
contract the gather path relies on).

How many copies that takes. Where a token stores much (multi-head K and V
of 1280 or 2048 lanes: 40 and 64 KB a block), one DMA a block and pool, as
ever: such a walk is at its bytes. A NARROW cache's block is small (16 KB
and 4 KB for a latent cache, `narrow`): block by block its walk is bound by
the count of copies, not by bytes, and it takes a table's RUNS whole.
Consecutive block ids are one contiguous span of a layer of the pool (the
kernels take it as its rows, `[L, NB*BS, width]`: the same bytes), so what
a table names IN A ROW goes in one copy a pool. Of each chunk of the table
the wrapper counts, beside the kernel, how many entries from its first on
are consecutive ids (`Tables.runs`, a fourth prefetched scalar array: no
search in the kernel's scalar loop, whose iterations cost as much as the
copies they would save); the walk fetches that many of the chunk's live
blocks in the binary pieces of their count, the largest first (DMA shapes
are static), and the rest a block at a time. A full chunk of one run is ONE
copy a pool where it was 16; a sequence's last, shorter chunk at most five;
a chunk whose ids do not follow each other a block at a time, as every
chunk was before the allocator handed out runs (serving/kv_cache.py
`BlockAllocator`; `run_chunks` is the host's count of the chunks the walk
takes whole). Only blocks the slot owns are ever fetched, the pieces put
the same bytes in the same buffer rows as the single blocks would, and
start and wait of a chunk read the same numbers: the result does not depend
on where a sequence's blocks lie.

How long a chunk is. A wide cache's is `_CHUNK` tokens, a narrow one's
`_SUB` = 512: what a chunk costs beside its bytes (the wait for its copies,
the MXU's fill and drain around two small products, the carry from the
chunk before) is paid once a chunk whatever it holds. Under a LONG table
(`_LONG_TABLE` tokens or more: rows of ten thousand tokens are twenty such
chunks a layer) a narrow cache's chunk is `_LONG_CHUNK` tokens: the copies
take it whole as ever (one copy a pool where it is one run; the ladder of
pieces has one more size), and the STEP goes through it in sub-tiles of
`_SUB` tokens in one straight line, scores `[M, _SUB]` as ever, the next
sub-tile's scores formed before this one's carry is consumed, and in a
row's last chunk up to the sub-tile that holds its newest token and no
further (`_softmax_chunk`). A long chunk that holds a break would go a
block a copy from the break on, up to 63 blocks; a prompt's blocks and its
growth's are TWO runs, so of a long chunk the tables count the run after
the first break as well (`Tables.runs`' second half) and it goes in pieces
too. `chunk_tokens` is the one number; the wrapper's count, the scratch and
the allocator's `run_chunk_share` go by it.

A block-sparse layer's LISTS (`paged_sparse_attention`: every (slot, K/V
head) pair reads `topk` blocks of its own choice, a few thousand tokens
whatever the context) are walked a SLOT a grid step, its K/V heads
together (`_sparse_kernel`). The buffers are a pool's full lanes wide and
a K/V head's tokens lie in its own lanes of them, so the step is the
grouped-query walk's one block-diagonal product for all query heads. Of a
slot's lists the first entries and the last are the same in every head's
(the forced blocks: the first and the newest window's, 33 or 34 of 64 in
`minicpm_sala.longdoc_sessions`; every entry of a row that reads its own
table): `pair_lists` counts both by a compare (`PairLists.shared`), and
those the walk fetches ONCE for all heads, a block one contiguous span of
its rows at all lanes and the window's run one copy of up to a megabyte a
pool, by runs counted from the list itself (`PairLists.runs`: of every
entry how many ids from it on follow each other, so a run is one ladder
of copies whatever boundary a table's chunks would have put inside it).
The entries between, a head's own picks, go that head's lanes of a block
a copy into that head's lanes of the buffer, a block at a time: picks
seldom follow each other, and a loop that asks costs every pick a branch.
The chunk is the lists' own number (`sparse_chunk_tokens`: 1024, 2048 or
4096 tokens, the longest that a sparse row's list fills; the step through
it in sub-tiles of `_SUB` as a long table's); `chunk_tokens`, the
allocator and the three other walks do not know it. A chunk's copies are
waited for by their BYTES, in a few waits of static sizes, and no list is
read a second time: a turn of the scalar core's loop costs as much as 16 KB
take to arrive. Measured alone at the cell's shapes (32 slots, lists of
64 blocks of 64 tokens, sequences of 17k-45k tokens in runs; us a layer;
PERF.md section 6, PR 57): a pair a grid step, one head's lanes a copy,
512 tokens a chunk 514 (its copies alone 361, its steps alone 257: they
add up, both turn on the scalar core); with 2048 tokens a chunk 343; a
slot a grid step with the shared entries at full width 463 at 512 tokens
a chunk and 387 at 4096; the waits by bytes 283; the picks a block at a
time 247, of which the copies alone are 245 (134 MB at 545 GB/s: the
copy engine bounds it) and the steps alone 107. Without the shared copy
302, without the runs 275, at 2048 / 1024 / 512 tokens a chunk 248 / 282 /
344.

An entry stored at a RATE (serving/kv_cache.py `KVCacheConfig.rated`: the
compressed keys a block-sparse attention scores before it reads any K or V)
lies a block a ROW, `[L, NB, E * W]`, NB whole tiles of 8 rows, and a row
is under a tile, which is the least a copy can address.
`paged_select_scores` walks such a pool by the same discipline with units
of its own (`Tables.rows`, `_select_kernel`): a PIECE is up to
`_PIECE` consecutive block ids, fetched in one copy from the tile of its
first block on, `_ROWS` rows of the layer or `_SHORT_ROWS` where the piece
is short, and a piece's scores are turned to their place in the table in
VMEM. Only the block scores leave the chip's VMEM.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

# which route each decode program's trace took ("paged" | "paged_gqa" |
# "paged_latent" | "paged_sparse" | "gather"), as attention's counts, and,
# of a program whose model scores a rated entry before it reads K and V, how
# it reads that entry ("select_paged": `paged_select_scores` | "select_gather":
# every slot's whole table gathered); DecodeEngine.status() reports them
GATE_COUNTS: collections.Counter = collections.Counter()
# tokens a chunk of a route whose walk has a number of its own (not
# `chunk_tokens`, which `status()["kv"]["walk_chunk_tokens"]` names): the
# sparse walk's (`sparse_chunk_tokens`), noted where its call is traced
WALK_CHUNKS: dict = {}


def gate_report() -> dict:
    """`GATE_COUNTS` for `DecodeEngine.status()["decode_attention"]`, with
    `<route>_chunk_tokens` of a counted route in `WALK_CHUNKS`."""
    return {**GATE_COUNTS, **{f"{route}_chunk_tokens": chunk
                              for route, chunk in WALK_CHUNKS.items()
                              if GATE_COUNTS.get(route)}}

# tokens a compute step: a multiple of 128, so scores [M, _CHUNK] fill
# their lanes; K and V double-buffered are 4 * _CHUNK * H*D elements of VMEM
_CHUNK = 256
# a NARROW cache: `_CHUNK` tokens of both pools are this many bytes at most
# (4 K/V heads of 128 in bf16, a 16 KB block a pool as the latent cache's, are
# the widest: no cache lay AT the limit before a model of that shape came)
_NARROW_CHUNK_BYTES = 512 * 1024
# a narrow cache's walk takes `_SUB` tokens a compute step; over a table of
# `_LONG_TABLE` tokens or more it fetches `_LONG_CHUNK` a chunk, and the
# step goes through the chunk in sub-tiles of `_SUB` (`narrow`)
_SUB = 2 * _CHUNK
_LONG_CHUNK = 1024
_LONG_TABLE = 16384

# a pool of rated entries `[L, NB, lanes]` holds a block a row, and a copy
# addresses whole tiles: `_ROW_TILE` rows of 2- and of 4-byte lanes alike
# (`kv_cache.RATED_ROW_TILE` makes NB whole tiles). A copy of its walk takes
# `_ROWS` blocks from the tile of a piece's first block on, so a piece is
# `_PIECE` blocks at most; one of `_SHORT_PIECE` at most takes `_SHORT_ROWS`
_ROW_TILE = 8
_ROWS = 128
_PIECE = _ROWS - _ROW_TILE
_SHORT_ROWS = 32
_SHORT_PIECE = _SHORT_ROWS - _ROW_TILE
# what that walk may hold of a core's memories (`use_paged_select`): the
# tables and the pieces' lengths in the scalar memory (half of a v5e's
# 1 MiB), two copies and a slot's scores in the vector memory (half of
# what a kernel is given unasked)
# a piece costs that walk what this many entries of a gathered table cost
# the gather it replaces (a v5e: 0.45 us a short piece, 0.024 us an entry)
_PIECE_ENTRIES = 20
_SELECT_SMEM_BYTES = 512 * 1024
_SELECT_VMEM_BYTES = 8 * 1024 * 1024

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def narrow(token_bytes: int) -> bool:
    """Whether a cache whose token stores `token_bytes` in a layer, in BOTH
    pools, is one whose walk is bound by what a chunk costs beside its
    bytes, and not by the bytes: the latent cache (512 + 128 lanes, a 16 KB
    and a 4 KB block) and the grouped-query one (2 x 256 lanes). What this
    decides, from shapes alone (measured on a v5e, PERF.md section 6,
    PR 40):

    - such a walk takes a table's RUNS of consecutive blocks in one copy a
      pool (`_walk`): block by block it reached 27% and 25% of the HBM
      peak, over runs at 256 tokens a chunk 1.86 and 1.92 times that.
      Multi-head K and V of 1280 or 2048 lanes (40 and 64 KB a block) are
      at their bytes block by block, 0.79 and 0.82 of the peak, and gain
      nothing from runs; the run branch's scalar work cost GPT-2's kernel
      2 us of 92 over scattered tables and, over the mostly idle slots of
      an open loop, 0.6 us of 12.9 a call, 36 calls a step: its call is
      the one it always was (`_call_form`), and `chat_open`'s decode
      program takes the device time it took, to four digits;
    - and it takes 512 tokens a compute step for `_CHUNK`'s 256
      (`chunk_tokens`): a chunk's fixed cost, some 0.5 us of the scalar
      core's turn and the pipeline's fill, is as long as 256 such tokens
      take to arrive (320 KB, 256 KB); at 512 the two walks over runs were
      another 1.33 and 1.36 times faster, 2.5 and 2.6 times the
      block-by-block walk (67% and 64% of the peak);
    - and under a table of `_LONG_TABLE` tokens or more, `_LONG_CHUNK`
      tokens a chunk with the step in sub-tiles of `_SUB` (`chunk_tokens`;
      PERF.md section 6, PR 46, the latent walk alone at 32 slots x
      8k-15k tokens, tables 20480 wide, us a call): 743 at 512 tokens a
      chunk, of which the copies alone take 624 (722 GB/s: the floor) and
      the step alone 434; 1024 tokens a chunk with nothing else changed
      672; with the step in sub-tiles 669, and 643 once the run after a
      chunk's first break goes in pieces too (a table there is a prompt's
      run and its growth's); 2048 and 4096 tokens a chunk 653 and 671
      (more blocks behind a break, more dead tokens fetched in a row's
      last chunk). Tables of ONE run each: 721 -> 644 / 642 / 643 at 1024
      / 2048 / 4096. The same walk at 32 x 1.2k-4.1k tokens under tables
      of 4608 read 192 -> 169; it keeps 512 all the same: a threshold
      that low would move the grouped-query walk of a 9216-token table
      with it, which this PR did not measure."""
    return _CHUNK * token_bytes <= _NARROW_CHUNK_BYTES


def chunk_tokens(token_bytes: int, table_tokens: int) -> int:
    """Tokens the walk fetches a chunk, from the cache's token bytes and the
    table's width in tokens (`max_blocks * block_size`, a static shape)
    alone: `_CHUNK`; `_SUB` for a `narrow` cache; `_LONG_CHUNK` for a narrow
    cache under a table of `_LONG_TABLE` tokens or more. THE one number
    that `blocks_per_chunk`, `with_runs`, `_scratch` and the allocator's
    count (`serving/kv_cache.BlockAllocator.per_chunk`) go by."""
    if not narrow(token_bytes):
        return _CHUNK
    return _LONG_CHUNK if table_tokens >= _LONG_TABLE else _SUB


def blocks_per_chunk(block_size: int, token_bytes: int,
                     table_tokens: int) -> int:
    """Blocks of `block_size` tokens the walk fetches a chunk: what one
    copy of a narrow cache's walk can take at most
    (`serving/kv_cache.run_chunks` counts by it)."""
    return max(1, chunk_tokens(token_bytes, table_tokens) // block_size)


def _token_bytes(*pools) -> int:
    return sum(p.shape[3] * p.dtype.itemsize for p in pools)


def _tiles(pool: jax.Array) -> bool:
    """Whether a pool `[L, NB, BS, width]` is one the kernels can address:
    a block is whole tiles, fetched `chunk_tokens // BS` to a chunk."""
    if pool.ndim != 4 or pool.dtype.itemsize not in (2, 4):
        return False
    bs, width = pool.shape[2:]
    return (width % 128 == 0 and bs % (32 // pool.dtype.itemsize) == 0
            and _CHUNK % bs == 0)


def _on_one_tpu(q: jax.Array) -> bool:
    """The computation runs on a TPU, outside a mesh that would have to
    partition a Mosaic kernel."""
    return _attention._platform(q) == "tpu" \
        and _attention._mesh_partitionable(q)


def use_paged(q: jax.Array, pool: jax.Array, heads: int) -> bool:
    """Whether multi-head decode attention takes the kernel: on one TPU,
    over pools `[L, NB, BS, H*D]` whose blocks are whole tiles."""
    if not _tiles(pool) or q.dtype != pool.dtype:
        return False
    hd = pool.shape[3]
    head_dim = hd // heads
    return (_on_one_tpu(q) and heads * head_dim == hd
            and (head_dim == 64 or head_dim % 128 == 0))


def use_paged_gqa(q: jax.Array, pool: jax.Array, heads: int,
                  kv_heads: int) -> bool:
    """Whether grouped-query decode attention takes the kernel: on one
    TPU, over pools `[L, NB, BS, kv_heads*D]` whose blocks are whole tiles,
    D whole lane tiles and the query heads whole sublane tiles; under ONE
    K/V head (multi-query attention) any count of query heads, which
    `paged_gqa_attention` fills up to whole tiles with rows of zeros."""
    if not _tiles(pool) or q.dtype != pool.dtype:
        return False
    head_dim = pool.shape[3] // kv_heads
    return (_on_one_tpu(q) and kv_heads * head_dim == pool.shape[3]
            and heads % kv_heads == 0 and head_dim % 128 == 0
            and (heads % _query_tile(pool) == 0 or kv_heads == 1))


def _query_tile(pool) -> int:
    """Rows of a sublane tile of the query block, in the pools' dtype."""
    return 32 // pool.dtype.itemsize


def use_paged_sparse(q: jax.Array, pool: jax.Array, heads: int,
                     kv_heads: int) -> bool:
    """Whether block-sparse grouped-query decode attention takes the
    kernel: where `paged_gqa_attention` would, with a K/V head's query
    heads whole sublane tiles (its walk is a K/V head's own) and a cache
    narrow enough that its pools are taken as rows (`narrow`)."""
    group = heads // max(kv_heads, 1)
    return (use_paged_gqa(q, pool, heads, kv_heads)
            and group % (32 // pool.dtype.itemsize) == 0
            and narrow(_token_bytes(pool, pool)))


def _select_scratch(pool, max_blocks: int, heads: int, kv_heads: int,
                    per_block: int):
    """`paged_select_scores`' scratch: two copies of `_ROWS` rows, a
    slot's scores a (tile of the table, K/V head, entry) and one tile more
    (a piece's last lanes may lie in the tile after its first), the
    copies' semaphores, the count of pieces consumed."""
    tiles = -(-max_blocks // _ROWS)
    return [pltpu.VMEM((2, _ROWS, pool.shape[2]), pool.dtype),
            pltpu.VMEM((tiles + 1, kv_heads, per_block, heads // kv_heads,
                        _ROWS), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32)]


def use_paged_select(q: jax.Array, pool: jax.Array, heads: int,
                     kv_heads: int, per_block: int, per_sel: int,
                     max_blocks: int) -> bool:
    """Whether a block-sparse attention's scores of its compressed keys
    take the kernel (`paged_select_scores`): on one TPU, over a pool of
    rated entries `[L, NB, per_block * kv_heads * D]` whose row is a
    block's `per_block` entries of every K/V head's D lanes, D whole lane
    tiles, NB whole tiles of rows and a copy's rows at least, a K/V
    head's query heads whole sublane tiles, a selection block ONE cache
    block (`per_sel` 1: the kernel scores a row a block), and tables
    `[slots of q, max_blocks]` that the scalar memory holds twice over
    beside scores that the vector memory holds."""
    if pool.ndim != 3 or pool.dtype.itemsize not in (2, 4) \
            or q.dtype != pool.dtype or per_sel != 1:
        return False
    width = per_block * kv_heads * 128
    group = heads // max(kv_heads, 1)
    if not (_on_one_tpu(q) and pool.shape[2] % width == 0
            and group * kv_heads == heads and pool.shape[1] >= _ROWS
            and pool.shape[1] % _ROW_TILE == 0
            and group % (32 // pool.dtype.itemsize) == 0):
        return False
    vmem = sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in _select_scratch(pool, max_blocks, heads, kv_heads,
                                        per_block)[:2])
    return (2 * q.shape[0] * max_blocks * 4 <= _SELECT_SMEM_BYTES
            and vmem <= _SELECT_VMEM_BYTES)


def use_paged_latent(q: jax.Array, c_pool: jax.Array, r_pool: jax.Array,
                     heads: int) -> bool:
    """Whether latent decode attention takes the kernel: on one TPU, over
    two pools of whole tiles that share their blocks, with the heads a
    whole number of sublane tiles."""
    return (_tiles(c_pool) and _tiles(r_pool)
            and q.dtype == c_pool.dtype == r_pool.dtype
            and c_pool.shape[:3] == r_pool.shape[:3]
            and heads % (32 // c_pool.dtype.itemsize) == 0
            and _on_one_tpu(q))


def _walk(layer_ref, tables_ref, pos_ref, lead_ref, pools, bufs, sems,
          done_ref, zeroed, setup, step, bs):
    """One grid step's walk over slot `program_id(0)`'s live blocks of
    layer `layer_ref[0]` of `pools` (`_call_form`; `bs` tokens a block):
    chunk by chunk through the double buffers `bufs` (one `[2, chunk,
    width]` a pool, `sems` `[2, len(pools)]`), one DMA a pool for every
    piece of consecutive blocks (`each_copy`; `lead_ref` `[S, chunks]`:
    `Tables.runs`), by `_through_chunks`' discipline (its `setup`, `step`,
    `zeroed` and result)."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    chunk = bufs[0].shape[1]
    per_chunk = chunk // bs
    max_blocks = tables_ref.shape[1]
    layer = layer_ref[0]
    sizes = [1 << i for i in reversed(range(per_chunk.bit_length()))]
    take_runs = lead_ref is not None    # a narrow cache's: `_call_form`
    # a long chunk's tables count the run after the first break too
    # (`with_runs`: the second half of `Tables.runs`)
    n_chunks = -(-max_blocks // per_chunk)
    two_runs = take_runs and lead_ref.shape[1] == 2 * n_chunks

    def live_blocks(slot):
        live = jnp.minimum(pos_ref[slot] // bs + 1, max_blocks)
        return jnp.where(tables_ref[slot, 0] == 0, 0, live)

    def each_copy(slot, c, buf, act):
        """`act` ("start" | "wait") on the copies of the live blocks of
        chunk `c` of `slot` into buffer `buf`: the blocks the table names
        in a row from the chunk's first (`lead_ref`: `Tables.runs`) in
        the binary pieces of their count, the largest first (a full chunk
        of one run: ONE copy a pool), the rest a block at a time; in a
        long chunk (`_SUB` < chunk) the run that follows the first break
        in pieces as well, so that a prompt's blocks and its growth's are
        a few copies where they meet. Start and wait read the same
        numbers, so they make the same copies."""
        first = c * per_chunk
        n = jnp.clip(live_blocks(slot) - first, 0, per_chunk)

        def copy(j, k):
            blk = tables_ref[slot, first + j]
            # a narrow cache's pools come as rows (`_call_form`): k blocks are
            # one span; a wide cache's as they lie, a block a copy
            src = pl.ds(pl.multiple_of(blk * bs, bs), k * bs) \
                if take_runs else blk
            rows = pl.ds(pl.multiple_of(j * bs, bs), k * bs)
            for which, (hbm, dst) in enumerate(zip(pools, bufs)):
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, src], dst.at[buf, rows],
                    sems.at[buf, which]), act)()

        def one(j, carry):
            copy(j, 1)
            return carry

        if not take_runs:       # a wide cache: a block is a large copy
            lax.fori_loop(0, n, one, 0)
            return

        # a scalar branch costs some 25 cycles and a narrow chunk's bytes
        # 400: the two common cases, a full chunk of one run and a chunk of
        # scattered blocks, take few
        run = jnp.minimum(lead_ref[slot, c], n)
        whole = run == per_chunk

        @pl.when(whole)
        def _():
            copy(0, per_chunk)

        def in_pieces(run, at=None):
            """The `run` blocks from the chunk's entry `at` on (None: its
            first, and the trace a short chunk always had), in the binary
            pieces of their count."""
            for k in sizes[1:]:
                @pl.when(run & k != 0)
                def _(k=k):
                    j = run & -(2 * k)              # larger ones lie before
                    copy(j if at is None else at + j, k)

        @pl.when(jnp.logical_not(whole))
        def _():
            pieces = run > 1        # a lone block goes with the rest
            pl.when(pieces)(functools.partial(in_pieces, run))
            done = jnp.where(pieces, run, 0)
            if two_runs:
                after = jnp.where(pieces, jnp.minimum(
                    lead_ref[slot, n_chunks + c], n - run), 0)
                after = jnp.where(after > 1, after, 0)
                pl.when(after > 0)(functools.partial(in_pieces, after, run))
                done = done + after
            lax.fori_loop(done, n, one, 0)

    return _through_chunks(
        s, n_slots, lambda slot: (live_blocks(slot) + per_chunk - 1)
        // per_chunk, each_copy, done_ref, zeroed, setup, step)


def _through_chunks(s, n_slots, chunks_of, each_copy, done_ref, zeroed,
                    setup, step):
    """Grid step `s` of `n_slots`: slot s's `chunks_of(s)` chunks through
    the double buffers, the next chunk (or the next slot's first) in
    flight while `step(query, c, buf, carry) -> carry` consumes chunk `c`
    from buffer `buf`; `each_copy(slot, c, buf, "start" | "wait")` makes a
    chunk's copies. `setup() -> (query, first carry)` builds the slot's
    query operands; it runs AFTER the call's first copies are started, so
    that no DMA waits for it. `zeroed` are the buffers whose stale rows
    meet exact zero weights and so must be finite from the start. Returns
    (query, the last carry: the first, for a slot that reads nothing)."""
    @pl.when(s == 0)
    def _():
        done_ref[0] = 0
        for buf in zeroed:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        each_copy(0, 0, 0, "start")

    def start_next_slot(buf):
        @pl.when(s + 1 < n_slots)
        def _():
            each_copy(s + 1, 0, buf, "start")

    done = done_ref[0]          # chunks consumed so far: the buffers' turn
    chunks = chunks_of(s)
    query, carry = setup()

    @pl.when(chunks == 0)
    def _():
        start_next_slot(done % 2)

    def consume(c, carry):
        buf = (done + c) % 2

        @pl.when(c + 1 < chunks)
        def _():
            each_copy(s, c + 1, 1 - buf, "start")

        @pl.when(c + 1 == chunks)
        def _():
            start_next_slot(1 - buf)

        each_copy(s, c, buf, "wait")
        return step(query, c, buf, carry)

    carry = lax.fori_loop(0, chunks, consume, carry)
    done_ref[0] = done + chunks
    return query, carry


def _softmax_step(sc, vals, c, pos, carry, first=None):
    """One chunk of the online softmax: scores `sc` [M, chunk] (float32,
    scaled) of the tokens `c * chunk ...`, of which those `<= pos` count
    (and, under a window, `>= first`), against `vals` [chunk, width]."""
    m, l, acc = carry
    tok = c * sc.shape[1] + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    seen = tok <= pos if first is None else (tok <= pos) & (tok >= first)
    sc = jnp.where(seen, sc, _MASKED)
    m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(sc - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc = alpha * acc + jnp.dot(p.astype(vals.dtype), vals,
                                preferred_element_type=jnp.float32)
    return m_new, l, acc


def _softmax_init(rows: int, width: int):
    return (jnp.full((rows, 1), _MASKED, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, width), jnp.float32))


def _softmax_chunk(tile, chunk: int, c, pos, carry, newest=None,
                   first=None):
    """Chunk `c` of the online softmax. `tile(rows) -> (scores [M, rows]
    float32 and scaled, values [rows, width])` of those rows of the chunk's
    buffers. A chunk of `_SUB` tokens at most is one tile, as ever. A
    longer one (`chunk_tokens`: a narrow cache under a long table) is
    walked in sub-tiles of `_SUB`, so that scores stay `[M, _SUB]`: where
    every sub-tile holds a live token, in one straight line, the scores of
    sub-tile j + 1 formed before the carry of j is consumed (both operands
    lie in VMEM and nothing waits between them, so the compiler may put the
    one's `q . k^T` beside the other's `p . v`); in a row's LAST chunk, up
    to the sub-tile that holds `pos` and no further, so that no arithmetic
    is done on dead tokens whatever the chunk's length. Where `pos` is a
    position a ROW (the sparse walk's K/V heads may end apart), `newest` is
    the largest of them. `first`: the first key a WINDOWED walk may see (the
    tokens before it in its block are fetched with the block and masked)."""
    if chunk <= _SUB:
        return _softmax_step(*tile(slice(None)), c, pos, carry, first)
    n_sub = chunk // _SUB
    # the sub-tile that holds `pos`
    last = ((pos if newest is None else newest) - c * chunk) // _SUB

    def whole(carry):
        nxt = tile(pl.ds(0, _SUB))
        for j in range(n_sub):
            sc, vals = nxt
            if j + 1 < n_sub:
                nxt = tile(pl.ds((j + 1) * _SUB, _SUB))
            carry = _softmax_step(sc, vals, c * n_sub + j, pos, carry, first)
        return carry

    def part(carry):
        def one(j, carry):
            sc, vals = tile(pl.ds(pl.multiple_of(j * _SUB, _SUB), _SUB))
            return _softmax_step(sc, vals, c * n_sub + j, pos, carry, first)

        return lax.fori_loop(0, last + 1, one, carry)

    return lax.cond(last >= n_sub - 1, whole, part, carry)


def _kernel(layer_ref, tables_ref, pos_ref, lead_ref, q_ref, k_hbm, v_hbm,
            o_ref, kbuf, vbuf, sems, done_ref, *, heads: int, scale: float,
            block_size: int):
    hd = kbuf.shape[2]
    head_dim = hd // heads
    m_rows = -(-heads // 16) * 16

    def setup():
        pos = pos_ref[pl.program_id(0)]
        row = lax.broadcasted_iota(jnp.int32, (m_rows, hd), 0)
        col = lax.broadcasted_iota(jnp.int32, (m_rows, hd), 1)
        own = (col >= row * head_dim) & (col < (row + 1) * head_dim)
        q = jnp.broadcast_to(q_ref[...].astype(jnp.float32), (m_rows, hd))
        q = jnp.where(own, q, 0.0).astype(kbuf.dtype)
        return (pos, q, own), _softmax_init(m_rows, hd)

    def step(query, c, buf, carry):
        pos, q, _ = query

        def tile(rows):
            sc = lax.dot_general(q, kbuf[buf, rows], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
            return sc, vbuf[buf, rows]

        return _softmax_chunk(tile, kbuf.shape[1], c, pos, carry)

    (_, _, own), (m, l, acc) = _walk(
        layer_ref, tables_ref, pos_ref, lead_ref, (k_hbm, v_hbm),
        (kbuf, vbuf), sems, done_ref, (vbuf,), setup, step, block_size)
    # an inactive slot (l == 0) gives zeros; its row is never read
    ctx = jnp.where(own, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[...] = jnp.sum(ctx, axis=0, keepdims=True)


def _gqa_kernel(layer_ref, tables_ref, pos_ref, lead_ref, q_ref, k_hbm,
                v_hbm, o_ref, kbuf, vbuf, sems, done_ref, *, kv_heads: int,
                scale: float, block_size: int, first_ref=None):
    """`first_ref` `[S]` (a WINDOWED walk, `window_tables`: the tables, the
    positions and this count from the window's first block on): the first
    key of the walked tokens a slot may see."""
    def walk(setup, step):
        return _walk(layer_ref, tables_ref, pos_ref, lead_ref,
                     (k_hbm, v_hbm), (kbuf, vbuf), sems, done_ref, (vbuf,),
                     setup, step, block_size)

    _gqa_body(q_ref, o_ref, kbuf, vbuf, walk,
              lambda: pos_ref[pl.program_id(0)], kv_heads, scale,
              None if first_ref is None
              else lambda: first_ref[pl.program_id(0)])


def _gqa_body(q_ref, o_ref, kbuf, vbuf, walk, newest, kv_heads: int,
              scale: float, oldest=None):
    """A slot's grouped-query attention over what `walk(setup, step)`
    brings into `kbuf` / `vbuf` `[2, chunk, kv_heads * D]`: the query block
    `[heads, kv_heads * D]`, row h in its K/V head's lanes. `newest()` is
    the position of the slot's newest token, or one a K/V head (a tuple:
    `_sparse_kernel`'s lists may end apart; under 0, a head that reads
    nothing and gets zeros)."""
    heads, head_dim = q_ref.shape
    width = kbuf.shape[2]               # kv_heads * head_dim
    group = heads // kv_heads

    def setup():
        pos, last = newest(), None
        row = lax.broadcasted_iota(jnp.int32, (heads, width), 0)
        col = lax.broadcasted_iota(jnp.int32, (heads, width), 1)
        own = col // head_dim == row // group
        if isinstance(pos, tuple):
            last = functools.reduce(jnp.maximum, pos)
            pos = functools.reduce(
                lambda rest, g: jnp.where(row[:, :1] // group == g, pos[g],
                                          rest), range(1, kv_heads), pos[0])
            own = own & (pos >= 0)
        q = jnp.concatenate([q_ref[...]] * kv_heads, axis=1)
        q = jnp.where(own, q, jnp.zeros_like(q))
        first = None if oldest is None else oldest()
        return (pos, q, own, last, first), _softmax_init(heads, width)

    def step(query, c, buf, carry):
        pos, q, _, last, first = query

        def tile(rows):
            sc = lax.dot_general(q, kbuf[buf, rows], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
            return sc, vbuf[buf, rows]

        return _softmax_chunk(tile, kbuf.shape[1], c, pos, carry, last,
                              first)

    (_, _, own, _, _), (m, l, acc) = walk(setup, step)
    # row h keeps its own K/V head's lanes; an inactive slot gives zeros
    ctx = jnp.where(own, acc / jnp.where(l > 0, l, 1.0), 0.0)
    out = ctx[:, :head_dim]
    for g in range(1, kv_heads):
        out = out + ctx[:, g * head_dim:(g + 1) * head_dim]
    o_ref[...] = out.astype(o_ref.dtype)


def _sparse_kernel(layer_ref, tables_ref, pos_ref, runs_ref, shared_ref,
                   q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, done_ref, *,
                   kv_heads: int, scale: float, block_size: int):
    """One grid step: slot `program_id(0)`'s K/V heads TOGETHER, each over
    the list of blocks of its own (`PairLists`: rows `s * kv_heads + g` of
    the tables). The buffers are a pool's full lanes wide, and row t of
    one holds, a K/V head in its own lanes, the t-th token of THAT head's
    list: an entry that all the lists share (the first `shared_ref[s, 0]`
    and the last `shared_ref[s, 1]` of the live ones) is fetched once at
    full width, a block one contiguous span and a run of them one copy;
    the others a head at a time, its lanes of the pool into its lanes of
    the buffer. The step is `paged_gqa_attention`'s on what lies there:
    one block-diagonal product scores all query heads, each against its
    own head's tokens."""
    s, n_slots = pl.program_id(0), pl.num_programs(0)
    bs, layer = block_size, layer_ref[0]
    pools, bufs = (k_hbm, v_hbm), (kbuf, vbuf)
    per_chunk = kbuf.shape[1] // bs
    width = tables_ref.shape[1]
    sizes = [1 << i for i in reversed(range(per_chunk.bit_length()))]

    def seen(slot, g):
        """The newest token's index in the list of K/V head `g`: under 0
        for a list that starts with the null block."""
        pair = slot * kv_heads + g
        return jnp.where(tables_ref[pair, 0] == 0, -1, pos_ref[pair])

    def live(slot, g):
        return jnp.minimum(seen(slot, g) // bs + 1, width)

    def longest(slot):
        return functools.reduce(jnp.maximum,
                                [live(slot, g) for g in range(kv_heads)])

    def each_copy(slot, c, buf, act):
        """Chunk `c` of `slot`'s lists into buffer `buf`. "start": the
        shared entries at the lists' start by their runs of consecutive
        ids (`runs_ref`: from every entry on; a run in the binary pieces
        of its count, the largest first), every head's own picks a block
        at a time, the shared entries at the lists' end by their runs.
        "wait": a copy's semaphore counts BYTES, so the chunk's are waited
        for in a few waits of static sizes that sum to what was started,
        whole chunks of one head's lanes first and the rest in the binary
        pieces of its count, and no list is read a second time (a turn of
        a scalar loop costs some 90 cycles, as long as the 16 KB it would
        wait for take to arrive)."""
        first = c * per_chunk
        top = longest(slot)
        lead, trail = shared_ref[slot, 0], shared_ref[slot, 1]
        a = jnp.clip(lead - first, 0, per_chunk)
        b = jnp.clip(top - trail - first, a, per_chunk)
        end = jnp.clip(top - first, 0, per_chunk)
        ends = [jnp.clip(live(slot, g) - trail - first, a, per_chunk)
                for g in range(kv_heads)]
        if act == "wait":
            # in units of one head's lanes of a block
            units = kv_heads * (a + end - b) + sum(e - a for e in ends)

            def wait(rows):
                for which, dst in enumerate(bufs):
                    to = dst.at[buf, pl.ds(0, rows),
                                pl.ds(0, dst.shape[2] // kv_heads)]
                    pltpu.make_async_copy(to, to, sems.at[buf, which]).wait()

            for i in range(kv_heads):
                pl.when(units >= (i + 1) * per_chunk)(
                    functools.partial(wait, per_chunk * bs))
            for k in sizes[1:]:
                pl.when((units % per_chunk) & k != 0)(
                    functools.partial(wait, k * bs))
            return

        def copy(pair, j, k, g=None):
            """`k` blocks from entry `j` of list `pair` on, one span of the
            pools' rows: all lanes, or K/V head `g`'s into its own."""
            blk = tables_ref[pair, first + j]
            src = pl.ds(pl.multiple_of(blk * bs, bs), k * bs)
            rows = pl.ds(pl.multiple_of(j * bs, bs), k * bs)
            for which, (hbm, dst) in enumerate(zip(pools, bufs)):
                if g is None:
                    from_, to = hbm.at[layer, src], dst.at[buf, rows]
                else:
                    lanes = dst.shape[2] // kv_heads
                    own = pl.ds(g * lanes, lanes)
                    from_ = hbm.at[layer, src, own]
                    to = dst.at[buf, rows, own]
                pltpu.make_async_copy(from_, to, sems.at[buf, which]).start()

        def shared(x, y):
            """Entries `x .. y - 1` of the chunk, the same in every list,
            by their runs."""
            pair = slot * kv_heads

            def in_pieces(j, r):
                for k in sizes:
                    @pl.when(r & k != 0)
                    def _(k=k):         # larger pieces lie before
                        copy(pair, j + (r & -(2 * k)), k)

            def run(j):
                r = jnp.minimum(runs_ref[slot, first + j], y - j)
                lax.cond(r == 1, lambda: copy(pair, j, 1),
                         functools.partial(in_pieces, j, r))
                return j + r

            lax.while_loop(lambda j: j < y, run, x)

        shared(0, a)
        # a head's own picks go a block at a time: they seldom follow each
        # other, and a loop that asks costs every pick a branch
        for g in range(kv_heads):
            lax.fori_loop(a, ends[g], lambda j, _, g=g: copy(
                slot * kv_heads + g, j, 1, g), None)
        shared(b, end)

    def walk(setup, step):
        return _through_chunks(
            s, n_slots, lambda slot: (longest(slot) + per_chunk - 1)
            // per_chunk, each_copy, done_ref, (kbuf, vbuf), setup, step)

    _gqa_body(q_ref, o_ref, kbuf, vbuf, walk,
              lambda: tuple(seen(s, g) for g in range(kv_heads)), kv_heads,
              scale)


def _latent_kernel(layer_ref, tables_ref, pos_ref, lead_ref, ql_ref, qr_ref,
                   c_hbm, r_hbm, o_ref, cbuf, rbuf, sems, done_ref, *,
                   scale: float, block_size: int):
    last = (((1,), (1,)), ((), ()))

    def setup():
        # [heads, latent] with W_UK in it already, [heads, rotary lanes]
        return (pos_ref[pl.program_id(0)], ql_ref[...], qr_ref[...]), \
            _softmax_init(*ql_ref.shape)

    def step(query, c, buf, carry):
        pos, ql, qr = query

        def tile(rows):
            ctx = cbuf[buf, rows]   # keys AND values: a row a token, all heads
            sc = (lax.dot_general(ql, ctx, last,
                                  preferred_element_type=jnp.float32)
                  + lax.dot_general(qr, rbuf[buf, rows], last,
                                    preferred_element_type=jnp.float32)) \
                * scale
            return sc, ctx

        return _softmax_chunk(tile, cbuf.shape[1], c, pos, carry)

    # rbuf meets the mask alone; cbuf's stale rows meet zero weights
    _, (m, l, acc) = _walk(layer_ref, tables_ref, pos_ref, lead_ref,
                           (c_hbm, r_hbm), (cbuf, rbuf), sems, done_ref,
                           (cbuf,), setup, step, block_size)
    o_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


class Tables(NamedTuple):
    """Block tables `[S, MB]` with what the walk reads beside them: `runs`
    `[S, chunks]`, of each chunk of `blocks_per_chunk` entries how many from
    its first on are consecutive block ids (1 at least). The walk fetches
    that many of the chunk's live blocks in the binary pieces of their
    count, one copy where the whole chunk is a run, and never searches the
    table itself. Where a chunk is long (`chunk_tokens` over `_SUB`: a
    break would cost it up to 63 copies of a block a pool) `runs` is `[S,
    2 * chunks]`: in its second half, how many ids FOLLOW each other from
    the first break on (0: no break), which go in pieces as well.
    `decoder.decode_step` makes one ONCE a step, outside its layer loop
    (inside, XLA counts again every layer: three fusions and a
    reduce-window, 2 to 11 us at the cells' tables); the kernels take one
    wherever they take block tables, and count for a bare array in the
    call. `rows` `[S, MB]` is the same for the walk over a pool of rated
    entries (`with_rows`, `paged_select_scores`), whose pieces are cut at
    the runs' ends and not at a chunk's: of every entry, how many ids from
    it on are consecutive, `_PIECE` at most, and `few` (a bool) whether
    the live pieces are few enough for that walk to beat a gather of the
    whole tables. Both are there exactly where a step's model found that
    walk's gate open (`ServeModel.rated_tables`), and are how the layers'
    `attend_paged` know."""

    ids: jax.Array
    runs: Optional[jax.Array]     # None: a wide cache's walk reads none
    rows: Optional[jax.Array] = None    # None: no rated entry is walked
    few: Optional[jax.Array] = None
    # a WINDOWED walk's (`window_tables`): `ids` are the table from the
    # window's first block on, `newest` `[S]` the slot's position counted
    # from that block's first token and `first` `[S]` the first key it sees
    first: Optional[jax.Array] = None
    newest: Optional[jax.Array] = None


def with_runs(block_tables, k_pool, v_pool) -> Tables:
    """`block_tables` as `Tables` for a walk over these two pools (as they
    are, if they are). Entries past a sequence's blocks are the null block
    and end a run; the walk stops at the live blocks anyway.
    `serving/kv_cache.run_chunks` is the host's count of the chunks this
    finds whole."""
    if isinstance(block_tables, Tables):
        return block_tables
    ids = block_tables.astype(jnp.int32)
    slots, mb = ids.shape
    token_bytes = _token_bytes(k_pool, v_pool)
    if not narrow(token_bytes):     # its walk takes a block a copy
        return Tables(ids, None)
    bs = k_pool.shape[2]
    per_chunk = blocks_per_chunk(bs, token_bytes, mb * bs)
    chunks = -(-mb // per_chunk)
    t = jnp.pad(ids, ((0, 0), (0, chunks * per_chunk - mb)))
    t = t.reshape(slots, chunks, per_chunk)
    follows = (t[..., 1:] == t[..., :-1] + 1).astype(jnp.int32)
    runs = 1 + jnp.sum(jnp.cumprod(follows, axis=-1, dtype=jnp.int32),
                       axis=-1, dtype=jnp.int32)
    if per_chunk * bs > _SUB:
        # a long chunk: the entries after ONE break, the next run's length
        after = jnp.sum(jnp.cumsum(1 - follows, axis=-1, dtype=jnp.int32)
                        == 1, axis=-1, dtype=jnp.int32)
        runs = jnp.concatenate([runs, after], axis=1)
    return Tables(ids, runs)


def window_blocks(window: int, block_size: int) -> int:
    """Blocks the newest `window` keys can lie in, wherever the newest
    stands in its block: 129 for 2048 keys in blocks of 16."""
    return (int(window) - 1 + int(block_size) - 1) // int(block_size) + 1


def window_tables(block_tables, positions, window: int, k_pool,
                  v_pool) -> Tables:
    """`block_tables` `[S, MB]` as a WINDOWED walk takes them: slot s reads
    the keys `max(0, p - window + 1) .. p` (p = `positions[s]`), so its walk
    starts at the block of the first of them and is an ordinary walk over
    the table FROM THAT BLOCK ON, `window_blocks` entries wide whatever MB
    is, with the position counted from there (`newest`) and the first
    block's earlier keys masked (`first`). Counted once a step for all the
    layers of the window kind, as `with_runs` is; the runs are the shifted
    table's, so a ring's wrap is a break wherever the window crosses it."""
    ids = block_tables.astype(jnp.int32)
    mb = ids.shape[1]
    bs = k_pool.shape[2]
    pos = positions.astype(jnp.int32)
    lo = jnp.maximum(pos - (int(window) - 1), 0)
    fb = lo // bs
    at = fb[:, None] + jnp.arange(min(mb, window_blocks(window, bs)),
                                  dtype=jnp.int32)[None, :]
    # past the table's end nothing is live: the last entry stands in
    shifted = jnp.take_along_axis(ids, jnp.minimum(at, mb - 1), axis=1)
    return with_runs(shifted, k_pool, v_pool)._replace(
        first=lo - fb * bs, newest=pos - fb * bs)


def _next_after(marks: jax.Array, none: int) -> jax.Array:
    """Of every entry j of `marks` `[S, N]` (bool), the index of the first
    mark AFTER j, `none` where there is none. A cumulative minimum over a
    whole row is a window as wide as the row on a TPU (N * N compares), so
    it is taken in two levels, of 32 and of `N / 32`."""
    span = 32
    slots, n = marks.shape
    pad = -n % span
    at = jnp.where(marks, jnp.arange(n, dtype=jnp.int32)[None, :], none)
    at = jnp.pad(at, ((0, 0), (0, pad)), constant_values=none)
    at = at.reshape(slots, -1, span)
    inside = lax.cummin(at, axis=2, reverse=True)       # from j on, its span
    later = lax.cummin(inside[:, :, 0], axis=1, reverse=True)
    later = jnp.pad(later[:, 1:], ((0, 0), (0, 1)), constant_values=none)
    from_j = jnp.minimum(inside, later[:, :, None]).reshape(slots, -1)
    return jnp.pad(from_j[:, 1:n], ((0, 0), (0, 1)), constant_values=none)


def with_rows(tables: Tables, positions: jax.Array,
              block_size: int) -> Tables:
    """`tables` with what the walk over a pool of rated entries goes by,
    counted once a step and not once a layer as `runs` is. `rows[s, j]`:
    how many of the ids `j, j + 1, ...` of slot s's table follow each
    other, `_PIECE` at most, which is the piece that walk fetches in one
    copy when it stands at `j` (it then stands at `j + rows[s, j]`: no
    search in the kernel). `few`: whether the live blocks (slot s at
    `positions[s]`) are so few pieces that the walk costs less than a
    gather of every slot's whole table, a piece `_PIECE_ENTRIES` entries
    of it."""
    ids = tables.ids
    slots, mb = ids.shape
    starts = jnp.pad(ids[:, 1:] != ids[:, :-1] + 1, ((0, 0), (1, 0)),
                     constant_values=True)
    at = jnp.arange(mb, dtype=jnp.int32)[None, :]
    live = jnp.where(ids[:, 0] == 0, 0,
                     jnp.minimum(positions // block_size + 1, mb))
    pieces = jnp.sum(starts & (at < live[:, None]), dtype=jnp.int32) \
        + jnp.sum(live // _PIECE, dtype=jnp.int32)
    return tables._replace(
        rows=jnp.clip(_next_after(starts, mb) - at, 1, _PIECE),
        few=pieces * _PIECE_ENTRIES <= slots * mb)


def _call_form(kernel, layer, block_tables, positions, *pools):
    """How a walk's `pallas_call` is made: (the body, the prefetched scalar
    arrays, the pools as the body takes them). THE place where the cache's
    shape decides. A wide cache's call is what it always was: layer, tables
    and positions, pools `[L, NB, BS, width]`, a body that has no
    `lead_ref`. A narrow cache's prefetches the tables' runs as a fourth
    array and takes the pools as their rows `[L, NB*BS, width]`, the same
    bytes (a block is whole tiles): consecutive blocks are one contiguous
    span of rows, which one copy takes. WINDOWED tables (`window_tables`)
    bring their own positions and one more prefetched array, the first key
    a slot may see, which the body takes as `first_ref`."""
    tables = with_runs(block_tables, *pools)
    windowed = tables.first is not None
    if windowed:        # counted from the window's first block on
        positions = tables.newest
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32), tables.ids,
               positions.astype(jnp.int32))
    wide = tables.runs is None
    if not wide:
        scalars = (*scalars, tables.runs)
        pools = tuple(p.reshape(p.shape[0], -1, p.shape[3]) for p in pools)
    if windowed:
        # the window's first key a slot: one more prefetched array, the last
        n = len(scalars)
        scalars = (*scalars, tables.first.astype(jnp.int32))
        return (lambda *refs: kernel(
            *refs[:3], None if wide else refs[3], *refs[n + 1:],
            first_ref=refs[n])), scalars, pools
    if wide:
        return (lambda layer_ref, tables_ref, pos_ref, *refs: kernel(
            layer_ref, tables_ref, pos_ref, None, *refs)), scalars, pools
    return kernel, scalars, pools


def _scratch(k_pool, v_pool, max_blocks: int, chunk: Optional[int] = None):
    """The walk's double buffers under tables `max_blocks` wide, a chunk
    (`chunk_tokens`, unless the route has a number of its own) of a pool's
    lanes each."""
    if chunk is None:
        chunk = chunk_tokens(_token_bytes(k_pool, v_pool),
                             max_blocks * k_pool.shape[2])
    return [pltpu.VMEM((2, chunk, k_pool.shape[3]), k_pool.dtype),
            pltpu.VMEM((2, chunk, v_pool.shape[3]), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32)]


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    layer: jax.Array, block_tables: jax.Array,
                    positions: jax.Array, *, heads: int,
                    interpret: bool = False) -> jax.Array:
    """q `[S, H*D]` against layer `layer` of the pools `[L, NB, BS, H*D]`
    through block_tables `[S, MB]` (or `Tables`: the same with its runs
    counted): slot s attends key positions `0..positions[s]`, scaled by
    `1/sqrt(D)`, scores and softmax in float32, and gets its context
    `[H*D]` in q's dtype; a slot whose table starts with the null block
    gets zeros. `interpret` runs the kernel in the Pallas TPU interpreter
    (tests, off the chip)."""
    n_slots, hd = q.shape
    kernel = functools.partial(_kernel, heads=heads,
                               scale=1.0 / math.sqrt(hd // heads),
                               block_size=k_pool.shape[2])
    kernel, scalars, pools = _call_form(kernel, layer, block_tables,
                                        positions, k_pool, v_pool)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, 1, hd), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, 1, hd), lambda s, *_: (s, 0, 0)),
            scratch_shapes=_scratch(k_pool, v_pool, scalars[1].shape[1])),
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, q[:, None, :], *pools)
    return out[:, 0, :].astype(q.dtype)


def paged_gqa_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        layer: jax.Array, block_tables: jax.Array,
                        positions: jax.Array, *, heads: int, kv_heads: int,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """Grouped-query decode attention: q `[S, heads*D]` against layer
    `layer` of the pools `[L, NB, BS, kv_heads*D]` through block_tables
    `[S, MB]`; query head h reads K/V head `h // (heads / kv_heads)`. Slot
    s attends key positions `0..positions[s]` at `scale` (None:
    `1/sqrt(D)`; a model whose softmax is at another scale hands its own),
    scores and softmax in float32, and gets `[heads*D]` in q's dtype; a
    slot whose table starts with the null block gets zeros. Under `window`
    slot s attends the newest `window` of those keys alone, and its walk
    starts at their first block (`window_tables`, which a step's caller
    makes once for all its windowed layers and hands over as the tables).
    Query heads that are not
    whole sublane tiles (one K/V head alone: every query head is of its
    group wherever it sits) are filled up with rows of zeros, whose
    context, a plain mean of V, is dropped."""
    n_slots = q.shape[0]
    head_dim = k_pool.shape[3] // kv_heads
    spare = -heads % _query_tile(k_pool)
    if window is not None and getattr(block_tables, "first", None) is None:
        block_tables = window_tables(block_tables, positions, window,
                                     k_pool, v_pool)
    if spare:
        if kv_heads != 1:
            raise ValueError(
                f"{heads} query heads over {kv_heads} K/V heads are not "
                "whole sublane tiles, and rows of zeros would move the "
                "groups")
        out = paged_gqa_attention(
            jnp.pad(q, [(0, 0), (0, spare * head_dim)]), k_pool, v_pool,
            layer, block_tables, positions, heads=heads + spare,
            kv_heads=1, scale=scale, interpret=interpret)
        return out[:, :heads * head_dim]
    per_slot = lambda s, *_: (s, 0, 0)      # noqa: E731
    kernel, scalars, pools = _call_form(
        functools.partial(_gqa_kernel, kv_heads=kv_heads,
                          scale=1.0 / math.sqrt(head_dim)
                          if scale is None else float(scale),
                          block_size=k_pool.shape[2]),
        layer, block_tables, positions, k_pool, v_pool)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, heads, head_dim), per_slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, head_dim), per_slot),
            scratch_shapes=_scratch(k_pool, v_pool, scalars[1].shape[1])),
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_gqa_attention",
    )(*scalars, q.reshape(n_slots, heads, head_dim), *pools)
    return out.reshape(n_slots, heads * head_dim)


class PairLists(NamedTuple):
    """What the sparse walk reads of a step's (slot, K/V head) pairs
    (`pair_lists`): `ids` `[S * kv_heads, W]`, pair `s * kv_heads + g`'s
    blocks in the order its tokens count; `newest` `[S * kv_heads]`, the
    index in that list of the pair's newest token; `shared` `[S, 2]`, how
    many of a slot's live entries from the first on, and how many up to
    the last, are the same in every one of its lists: those the walk
    fetches once for all K/V heads, a block's lanes whole; `runs` `[S, W]`,
    of every entry of a slot's FIRST list (which the shared entries are
    read from) how many ids from it on follow each other (1 at least; the
    walk takes that many shared blocks in the binary pieces of their
    count, and never searches a list itself)."""

    ids: jax.Array
    newest: jax.Array
    shared: jax.Array
    runs: jax.Array


def pair_lists(tables: jax.Array, positions: jax.Array, kv_heads: int,
               block_size: int) -> PairLists:
    """`tables` `[S * kv_heads, W]` and `positions` `[S * kv_heads]`
    (`paged_sparse_attention`) with what its walk goes by counted: a
    compare of neighbours for the runs (the lists are there: no search of
    the block tables), a compare of a slot's lists for the entries they
    share. Lists of different live lengths share no entry at their ends,
    nor does a list that starts with the null block share any."""
    ids = tables.astype(jnp.int32)
    width = ids.shape[1]
    at = jnp.arange(width, dtype=jnp.int32)
    lists = ids.reshape(-1, kv_heads, width)
    first = lists[:, 0]
    starts = jnp.pad(first[:, 1:] != first[:, :-1] + 1, ((0, 0), (1, 0)),
                     constant_values=True)
    runs = _next_after(starts, width) - at[None, :]
    live = jnp.where(ids[:, 0] == 0, 0, jnp.minimum(
        positions.astype(jnp.int32) // block_size + 1, width))
    live = live.reshape(-1, kv_heads)
    least, top = jnp.min(live, axis=1), jnp.max(live, axis=1)
    same = jnp.all(lists == lists[:, :1], axis=1)               # [S, W]
    # the first live entry that differs, and the last: two reductions
    lead = jnp.min(jnp.where(same & (at < least[:, None]), width, at),
                   axis=1)
    differs = jnp.max(jnp.where(same | (at >= top[:, None]), -1, at), axis=1)
    trail = jnp.where(least == top, top - 1 - differs, 0)
    trail = jnp.clip(trail, 0, top - lead)
    return PairLists(ids, positions.astype(jnp.int32),
                     jnp.stack([lead, trail], axis=1), runs)


# the sparse walk's chunk: a LIST's tokens (a few thousand: `topk` blocks)
# are scattered blocks and a window's run, and its chunk is its own number,
# `sparse_chunk_tokens`: the longest of these that a row's usual list fills
# whole and whose double buffers stay under `_SPARSE_VMEM_BYTES`
_SPARSE_CHUNKS = (4096, 2048, 1024)
_SPARSE_VMEM_BYTES = 8 * 1024 * 1024


def sparse_chunk_tokens(token_bytes: int, list_tokens: int) -> int:
    """Tokens the sparse walk fetches a chunk, from static shapes alone:
    what a token stores in both pools (`token_bytes`, all K/V heads: a
    chunk's buffers are a pool's lanes wide) and the tokens of a sparse
    row's list (`list_tokens`: `topk` selection blocks; the table's width
    where the caller names none). The STEP goes through a chunk in
    sub-tiles of `_SUB` (`_softmax_chunk`), all of a chunk's copies are
    started at once and the next chunk's (the next slot's) are in flight
    meanwhile; a chunk longer than the list would fetch nothing more and
    hold VMEM for it. This is the LISTS' number: the allocator and the
    other walks go by `chunk_tokens`."""
    for chunk in _SPARSE_CHUNKS[:-1]:
        if chunk <= list_tokens \
                and 2 * chunk * token_bytes <= _SPARSE_VMEM_BYTES:
            return chunk
    return _SPARSE_CHUNKS[-1]


def paged_sparse_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, layer: jax.Array,
                           tables, positions: Optional[jax.Array] = None, *,
                           heads: int, kv_heads: int,
                           list_tokens: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Grouped-query decode attention in which every (slot, K/V head) reads
    a list of blocks OF ITS OWN: q `[S, heads*D]` against layer `layer` of
    the pools `[L, NB, BS, kv_heads*D]`; `tables` `[S*kv_heads, W]` holds,
    for slot s and K/V head g in row `s*kv_heads + g`, the block ids that
    pair reads, in the order its tokens count, and `positions`
    `[S*kv_heads]` the index IN THAT LIST of the pair's newest token (the
    tokens `0..positions` of the list are attended; the blocks before the
    last are whole); or `tables` is `PairLists` (`pair_lists`: the same
    with its runs and shared entries counted). A block-sparse layer hands
    over the blocks each K/V head selected; a row that reads everything
    its own table for every head. One grid step a SLOT (`_sparse_kernel`):
    what a slot's lists share goes once for all heads at a block's full
    lanes, the rest one K/V head's lanes a copy, runs of consecutive ids
    in one copy either way, `sparse_chunk_tokens` (of `list_tokens`, a
    sparse row's list; static) a chunk; a pair whose list starts with the
    null block gets zeros. -> `[S, heads*D]` in q's dtype."""
    bs = k_pool.shape[2]
    lists = tables if isinstance(tables, PairLists) \
        else pair_lists(tables, positions, kv_heads, bs)
    chunk = sparse_chunk_tokens(_token_bytes(k_pool, v_pool),
                                list_tokens or lists.ids.shape[1] * bs)
    WALK_CHUNKS["paged_sparse"] = chunk
    # jitted, so that a model's sparse layers, which make the same call,
    # share ONE trace of the kernel and one lowering (the interpreter's
    # parameters are no static argument: tests call it as it is)
    call = _sparse_call if interpret else _sparse_call_jitted
    return call(q, k_pool, v_pool, layer, lists, heads, kv_heads, chunk,
                interpret)


def _sparse_call(q, k_pool, v_pool, layer, lists: PairLists, heads: int,
                 kv_heads: int, chunk: int, interpret):
    n_slots = q.shape[0]
    bs = k_pool.shape[2]
    head_dim = k_pool.shape[3] // kv_heads
    per_slot = lambda s, *_: (s, 0, 0)      # noqa: E731
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, kv_heads=kv_heads,
                          scale=1.0 / math.sqrt(head_dim), block_size=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, heads, head_dim), per_slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, head_dim), per_slot),
            scratch_shapes=_scratch(k_pool, v_pool, lists.ids.shape[1],
                                    chunk)),
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_sparse_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lists.ids, lists.newest,
      lists.runs, lists.shared, q.reshape(n_slots, heads, head_dim),
      *(p.reshape(p.shape[0], -1, p.shape[3]) for p in (k_pool, v_pool)))
    return out.reshape(n_slots, heads * head_dim)


_sparse_call_jitted = jax.jit(_sparse_call, static_argnums=(5, 6, 7, 8))


def paged_latent_attention(q_latent: jax.Array, q_rope: jax.Array,
                           c_pool: jax.Array, r_pool: jax.Array,
                           layer: jax.Array, block_tables: jax.Array,
                           positions: jax.Array, *, scale: float,
                           interpret: bool = False) -> jax.Array:
    """Latent (MLA) decode attention in the absorbed form: the queries
    q_latent `[S, H, C]` (W_UK absorbed) and q_rope `[S, H, R]` against
    layer `layer` of the latent pool `[L, NB, BS, C]` and the rotary-key
    pool `[L, NB, BS, R]` through block_tables `[S, MB]`. Slot s attends
    positions `0..positions[s]` with scores `(q_latent . c + q_rope . r) *
    scale` (float32, as the softmax) and gets `sum a c` `[H, C]` a head in
    the queries' dtype, for W_UV to take on; a slot whose table starts
    with the null block gets zeros. Lanes of `q_rope` past the rotary
    width meet the pool's zero lanes."""
    n_slots, heads, latent = q_latent.shape
    rope = q_rope.shape[2]
    per_slot = lambda s, *_: (s, 0, 0)      # noqa: E731
    kernel, scalars, pools = _call_form(
        functools.partial(_latent_kernel, scale=scale,
                          block_size=c_pool.shape[2]),
        layer, block_tables, positions, c_pool, r_pool)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, heads, latent), per_slot),
                pl.BlockSpec((None, heads, rope), per_slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, latent), per_slot),
            scratch_shapes=_scratch(c_pool, r_pool, scalars[1].shape[1])),
        out_shape=jax.ShapeDtypeStruct(q_latent.shape, q_latent.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(*scalars, q_latent, q_rope, *pools)


def _select_kernel(layer_ref, tables_ref, pos_ref, rows_ref, q_ref, pool_hbm,
                   o_ref, buf, sc, sems, done_ref, *, kv_heads: int,
                   per_block: int, stride: int, block_size: int,
                   scale: float):
    """One grid step: slot `program_id(0)`'s block scores. The slot's live
    blocks are walked in PIECES of consecutive ids (`rows_ref`: `Tables
    .rows`), a piece one copy of layer `layer_ref[0]`'s rows from the tile
    of its first block on (the pool's last rows at the latest): `_ROWS` of
    them, or `_SHORT_ROWS` where the piece is at most `_SHORT_PIECE`
    blocks. Double-buffered as `_walk`'s chunks are: the next piece, or
    the next slot's first, is in flight while this one is scored. A
    product is a K/V head's query heads against 128 keys: one entry's of a
    long copy's rows, or every entry's of a short copy's, side by side.
    Its columns are turned to their place in the TABLE (a lane a block;
    `sc` `[tiles, kv_heads, per_block, group, 128]`), unseen entries
    masked, so that the softmax and the blocks' scores read whole tiles
    whatever the pieces were."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_blocks, lanes = pool_hbm.shape[1:]
    head_dim = lanes // (per_block * kv_heads)
    group = q_ref.shape[0] // kv_heads
    max_blocks = tables_ref.shape[1]
    layer = layer_ref[0]
    f32 = jnp.float32

    def live_blocks(slot):
        live = jnp.minimum(pos_ref[slot] // block_size + 1, max_blocks)
        return jnp.where(tables_ref[slot, 0] == 0, 0, live)

    def piece_blocks(slot, j):
        return jnp.minimum(rows_ref[slot, j], live_blocks(slot) - j)

    def first_row(slot, j, rows):
        return jnp.minimum(tables_ref[slot, j] // _ROW_TILE * _ROW_TILE,
                           n_blocks - rows)

    def each_size(slot, j, do):
        """`do(rows)` for the copy that holds the piece at `j`: start,
        wait and the products agree on it."""
        short = piece_blocks(slot, j) <= _SHORT_PIECE
        pl.when(short)(functools.partial(do, _SHORT_ROWS))
        pl.when(jnp.logical_not(short))(functools.partial(do, _ROWS))

    def copy(slot, j, b, act):
        def do(rows):
            at = pl.multiple_of(first_row(slot, j, rows), _ROW_TILE)
            getattr(pltpu.make_async_copy(
                pool_hbm.at[layer, pl.ds(at, rows)],
                buf.at[b, pl.ds(0, rows)], sems.at[b]), act)()

        each_size(slot, j, do)

    def start_next_slot(b):
        @pl.when(s + 1 < n_slots)
        def _():
            @pl.when(live_blocks(s + 1) > 0)
            def _():
                copy(s + 1, 0, b, "start")

    @pl.when(s == 0)
    def _():
        done_ref[0] = 0
        # rows past a piece are scored and masked: finite from the start
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

        @pl.when(live_blocks(0) > 0)
        def _():
            copy(0, 0, 0, "start")

    done = done_ref[0]          # pieces consumed so far: the buffers' turn
    n = live_blocks(s)
    seen_entries = (pos_ref[s] + 1) // stride
    lane = lax.broadcasted_iota(jnp.int32, (1, _ROWS), 1)

    @pl.when(n == 0)
    def _():
        start_next_slot(done % 2)

    def score(b, j, k, rows):
        """The piece of `k` blocks at table entry `j`, in the first `rows`
        rows of buffer `b`: buffer row `lead + i` holds entry `j + i`,
        which belongs in lane `at + i` of tile `tile` of the scores, or in
        the tile after."""
        lead = tables_ref[s, j] - first_row(s, j, rows)
        tile, at = j // _ROWS, j % _ROWS
        side = _ROWS // rows        # entries side by side in a product
        # of the two tiles' lanes, those this piece fills and the query
        # sees: entry 0 is no window, and a query sees the windows it
        # sees the last token of
        seen = []
        for e in range(per_block):
            seen.append([])
            for half in range(2):
                here = lane + half * _ROWS
                ent = (tile * _ROWS + here) * per_block + e
                seen[e].append((here >= at) & (here < at + k) & (ent >= 1)
                               & (ent < seen_entries))
        for g in range(kv_heads):
            for e0 in range(0, per_block, side):
                first = [(min(e0 + i, per_block - 1) * kv_heads + g)
                         * head_dim for i in range(side)]
                keys = [buf[b, :rows, c:c + head_dim] for c in first]
                product = lax.dot_general(
                    q_ref[g * group:(g + 1) * group, :],
                    keys[0] if side == 1 else jnp.concatenate(keys, axis=0),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale
                for e in range(e0, min(e0 + side, per_block)):
                    part = pltpu.roll(
                        product, (at - lead - (e - e0) * rows) % _ROWS, 1)
                    # lanes before the piece keep what earlier pieces put
                    # there; lanes after it are masked until a later
                    # piece fills them
                    sc[tile, g, e] = jnp.where(
                        lane < at, sc[tile, g, e],
                        jnp.where(seen[e][0], part, _MASKED))
                    sc[tile + 1, g, e] = jnp.where(seen[e][1], part,
                                                   _MASKED)

    def piece(carry):
        j, i = carry
        b = (done + i) % 2
        k = piece_blocks(s, j)

        @pl.when(j + k < n)
        def _():
            copy(s, j + k, 1 - b, "start")

        @pl.when(j + k >= n)
        def _():
            start_next_slot(1 - b)

        copy(s, j, b, "wait")
        each_size(s, j, functools.partial(score, b, j, k))
        return j + k, i + 1

    _, pieces = lax.while_loop(lambda c: c[0] < n, piece,
                               (jnp.int32(0), jnp.int32(0)))
    done_ref[0] = done + pieces

    # the group's softmax over all it sees, summed over its heads; a block
    # scores the largest of its own entries and the first of the next block
    tiles = (n + _ROWS - 1) // _ROWS
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    for g in range(kv_heads):
        def highest(t, m, g=g):
            for e in range(per_block):
                m = jnp.maximum(m, sc[t, g, e])
            return m

        top = jnp.max(lax.fori_loop(
            0, tiles, highest, jnp.full((group, _ROWS), _MASKED, f32)),
            axis=1, keepdims=True)

        def weigh(t, total, g=g, top=top):
            for e in range(per_block):
                v = sc[t, g, e]
                p = jnp.where(v > 0.5 * _MASKED, jnp.exp(v - top), 0.0)
                sc[t, g, e] = p
                total = total + p
            return total

        total = jnp.maximum(jnp.sum(lax.fori_loop(
            0, tiles, weigh, jnp.zeros((group, _ROWS), f32)),
            axis=1, keepdims=True), 1e-30)

        def blocks(i, nxt, g=g, total=total):
            t = tiles - 1 - i       # from the last: a block needs the next
            share = [jnp.sum(sc[t, g, e] / total, axis=0, keepdims=True)
                     for e in range(per_block)]
            own = functools.reduce(jnp.maximum, share)
            after = jnp.where(lane == _ROWS - 1, nxt,
                              pltpu.roll(share[0], _ROWS - 1, 1))
            o_ref[t, g:g + 1, :] = jnp.maximum(own, after)
            return jnp.sum(jnp.where(lane == 0, share[0], 0.0), axis=1,
                           keepdims=True)

        lax.fori_loop(0, tiles, blocks, jnp.zeros((1, 1), f32))


def paged_select_scores(q: jax.Array, pool: jax.Array, layer: jax.Array,
                        tables: Tables, positions: jax.Array, *,
                        kv_heads: int, stride: int, block_size: int,
                        interpret: bool = False) -> jax.Array:
    """What a block-sparse attention's selection scores its blocks by,
    read where the compressed keys lie: q `[S, heads * D]` (normalised,
    unscaled) against layer `layer` of the pool of rated entries `[L, NB,
    E * kv_heads * D]` (E = `block_size / stride` entries a block, entry e
    the window that completes in the e-th group of `stride` tokens: none
    in the first) through `tables` (`with_rows`: ids `[S, MB]` and the
    pieces' lengths). Slot s at position `positions[s]` sees the entries
    `1 .. (positions[s] + 1) // stride - 1`; a K/V head's query heads each
    take a softmax over them (`1/sqrt(D)`, float32) and sum it, and a
    block scores the largest sum of its own entries and the FIRST entry of
    the next block -> `[S, kv_heads, MB]` float32, 0 for a block the slot
    sees no entry of and for a slot whose table starts with the null block
    (`models/minicpm_sala.block_scores` is the same on gathered keys)."""
    n_slots, mb = tables.ids.shape
    lanes = pool.shape[2]
    per_block = block_size // stride
    head_dim = lanes // (per_block * kv_heads)
    heads = q.shape[1] // head_dim
    tiles = -(-mb // _ROWS)
    out = pl.pallas_call(
        functools.partial(_select_kernel, kv_heads=kv_heads,
                          per_block=per_block, stride=stride,
                          block_size=block_size,
                          scale=1.0 / math.sqrt(head_dim)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, heads, head_dim),
                             lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, tiles, kv_heads, _ROWS),
                                   lambda s, *_: (s, 0, 0, 0)),
            scratch_shapes=_select_scratch(pool, mb, heads, kv_heads,
                                           per_block)),
        out_shape=jax.ShapeDtypeStruct((n_slots, tiles, kv_heads, _ROWS),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_select_scores",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables.ids,
      positions.astype(jnp.int32), tables.rows,
      q.reshape(n_slots, heads, head_dim), pool)
    return jnp.swapaxes(out, 1, 2).reshape(n_slots, kv_heads, -1)[..., :mb]
