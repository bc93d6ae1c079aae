"""Paged (blocked) KV cache for autoregressive decode.

The classic serving memory problem (vLLM SOSP'23): a contiguous
per-sequence KV buffer must be sized for max_seq_len, so HBM scales
with max_len × batch even when most sequences are short — and XLA's
static shapes make "grow the buffer" a recompile. The paged design
keeps ONE preallocated pair of device pools of fixed-size blocks for all
layers (`[L, num_blocks, block_size, width]` each: what a token stores in
a layer lies in the lane dimension, so a block is contiguous on the TPU;
the MODEL says what the two entries are and how wide, `KVCacheConfig`:
multi-head K and V with a token's heads side by side, or a latent
cache's compressed vector and shared rotary key) plus a tiny
per-sequence *block table* mapping logical positions to pool blocks.
Memory then scales with LIVE TOKENS (rounded up to the block size),
sequences grow by appending a block id to their table — a host-side
int, never a new executable — and the decode executable's shapes stay
fixed no matter which sequences are resident.

Layering: this module owns the host-side `BlockAllocator` (free extents,
tables handed out as runs of consecutive ids, the count of the chunks the
decode kernels read with one copy: `run_chunks`) and the pure jnp pool
helpers
(`init_pools`, `write_token_kv`, `write_prefill_kv`, `write_chunk_kv`,
`write_span_kv`, `gather_kv`) that `models/decoder.py` composes into
the serve programs; they take the whole pool and a layer index, and
the layer loop carries the pools and addresses them in place. Who writes
in which unit: a whole prompt goes in a BLOCK at a time wherever its
bucket is whole blocks (`write_prefill_kv`: on a TPU one DMA a block,
ops/pallas/kv_block_write.py; `PREFILL_WRITE_UNITS` counts the unit);
the decode step, a prefill chunk and a verified span, whose positions
start anywhere, a token at a time. Who still
composes `gather_kv` (a padded copy of every slot's whole table, a
layer): `prefill_chunk` and `verify_step` everywhere, and `decode_step`
off the TPU (`decoder.cached_attention`); on a TPU `decode_step` reads
the live blocks through the table in a kernel
(`ops/pallas/paged_attention.py`), which relies on this layout: a block
is `BS` whole sublane tiles of the entry's lanes. The scheduler
that decides WHICH sequences own which blocks lives in
`serving/decode.py`.

A model may store MORE than the two entries a token: an entry at a RATE,
one every `stride` tokens (`KVCacheConfig.rated`: compressed keys that a
block-sparse attention scores before it reads any K/V). Such an entry has
a pool of its own, `[L, NB, BS/stride * width]`: the `BS/stride` entries of
a block side by side in the lanes of ONE row, so that a block of the
sequence's one table names them as it names its K and V (they are freed
with it), a block's entries are one contiguous span and the pool is whole
tiles whatever `BS/stride` is (`write_token_rated`, `write_blocks_rated`,
`gather_rated`).

Block 0 is reserved as the *null block*: padded/inactive decode slots
and out-of-range table entries all read and write it, so a fixed-shape
executable needs no validity branches — the attention length mask
already guarantees nothing read from the null block ever contributes.

A model may keep TWO KINDS of cache (`ServeModel.window`: layers that see
the newest `window` keys alone, among layers that see every key). Each kind
has pools, an allocator and a table a sequence of its own. The global kind
is everything above. The WINDOW kind hands a sequence blocks as it grows up
to a ring of `ring_blocks(window, slice, block_size)` and none after; its
table is the ring REPEATED (`window_table`: `table[j] = ring[j mod R]`), so
every writer here lands position `p` at block `p // BS` of the table
unchanged and overwrites what fell out of reach, and a sequence shorter
than the ring holds its own length.

Beside the block pools a model with recurrent layers keeps STATE ROW
pools (`StateRowAllocator`): a fixed-size row a sequence, allocated at
admission, overwritten by its prefill, advanced in place by every decode
step, freed at finish, cancel and preemption (a replay's prefill rebuilds
it). Row 0 is the null row, as block 0 is the null block.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import fractions
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KVCacheConfig", "BlockAllocator", "NoBlocksError", "run_chunks",
           "ring_blocks", "window_table",
           "StateRowAllocator", "NULL_ROW",
           "init_pools", "init_rated_pools", "write_token_kv",
           "write_prefill_kv", "write_chunk_kv", "write_span_kv",
           "write_token_rated", "write_blocks_rated", "gather_kv",
           "gather_rated",
           "NULL_BLOCK", "PREFILL_WRITE_UNITS"]

NULL_BLOCK = 0
NULL_ROW = 0
# rows of a TPU tile, of 2- and of 4-byte lanes alike: a pool that holds a
# block a row has whole tiles of them (`KVCacheConfig.rated_pool_shapes`)
RATED_ROW_TILE = 8

# which unit each traced whole-prompt write took ("blocks" | "rows"), one
# count a call of write_prefill_kv: a prefill program counts two, K and V.
# DecodeEngine.status() reports them beside the decode attention's route.
PREFILL_WRITE_UNITS: collections.Counter = collections.Counter()


class NoBlocksError(RuntimeError):
    """The pool has fewer free blocks than the allocation needs (the
    scheduler reacts by deferring admission or preempting a sequence —
    never by growing the pool, whose size is baked into executables)."""


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Shape of the device pools. `max_len` bounds any single sequence
    (prompt + generated) and fixes the block-table width every decode
    executable is compiled against.

    A token stores TWO entries in a layer, one in each pool, and how wide
    each is the model says (`models/decoder.ServeModel.stored`); nothing
    here or in the engine knows what they hold, and the engine passes
    `widths` alone. `kv_heads` and `head_dim` are the shorthand of a caller
    that has no model at hand: multi-head attention's K and V,
    `kv_heads*head_dim` lanes each. A
    latent (MLA) cache stores the normalised compressed vector, 512 lanes,
    and the one rotated key part all heads share, 64 values in a pool of
    128 lanes: `widths=(512, 128)`, 1280 bytes a token a layer in bf16 for
    1152 of content. The 64 empty lanes are the price of the layout: the
    TPU tiles the lane dimension by 128, so a 64-wide pool takes the same
    HBM and every helper and kernel here (a block = whole tiles, a run of
    blocks one DMA) serves both pools as it is; one padded row of 640 lanes
    costs the same bytes and would want a single-pool engine beside this
    one."""

    layers: int
    max_len: int
    block_size: int = 16
    num_blocks: int = 64
    dtype: str = "bfloat16"
    widths: Optional[Tuple[int, int]] = None
    kv_heads: int = 0
    head_dim: int = 0
    # entries stored at a rate beside the two: (lanes, stride in tokens)
    # each, one entry every `stride` tokens (`ServeModel.rated`)
    rated: Tuple[Tuple[int, int], ...] = ()

    def __post_init__(self):
        for width, stride in self.rated:
            if stride < 1 or self.block_size % stride:
                raise ValueError(
                    f"an entry every {stride} tokens needs a block size "
                    f"of whole strides, got {self.block_size}")

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-int(self.max_len) // int(self.block_size))

    @property
    def usable_blocks(self) -> int:
        return int(self.num_blocks) - 1  # block 0 is the null block

    @property
    def entry_widths(self) -> Tuple[int, int]:
        """Lanes of the two entries a token stores in a layer."""
        if self.widths is not None:
            return (int(self.widths[0]), int(self.widths[1]))
        return (int(self.kv_heads * self.head_dim),) * 2

    @property
    def pool_shapes(self) -> Tuple[Tuple[int, int, int, int],
                                   Tuple[int, int, int, int]]:
        """`[L, NB, BS, width]` of each pool: a token's entry lies in the
        minor-most (lane) dimension (multi-head: its heads side by side)
        and the `BS` slots of a block in the sublanes above it, so on the
        TPU a block is one contiguous, unpadded run of tiles and (layer,
        block, slot) address it without a re-layout."""
        return tuple((int(self.layers), int(self.num_blocks),
                      int(self.block_size), w) for w in self.entry_widths)

    @property
    def pool_shape(self) -> Tuple[int, int, int, int]:
        """The one shape of both pools, where the entries are alike."""
        a, b = self.pool_shapes
        if a != b:
            raise ValueError(f"the pools differ in shape: {a} and {b}")
        return a

    @property
    def rated_pool_shapes(self) -> Tuple[Tuple[int, int, int], ...]:
        """`[L, NB8, BS/stride * width]` of each rated entry's pool: a
        block's entries side by side in the lanes of one row, a block a
        row, and the rows made up to whole tiles of `RATED_ROW_TILE` (NB8:
        `num_blocks` rounded up; no table names the rows past `num_blocks`
        and `pool_bytes` does not count them). A pool of whole tiles lies
        on a TPU as its shape says, so a kernel that walks a layer's rows
        (`ops/pallas/paged_attention.py paged_select_scores`) takes it
        where it lies; `[2, 24577, 1024]` lay with its LAYERS minor."""
        rows = -(-int(self.num_blocks) // RATED_ROW_TILE) * RATED_ROW_TILE
        return tuple((int(self.layers), rows,
                      int(self.block_size) // int(stride) * int(width))
                     for width, stride in self.rated)

    def walk_bytes_per_token(self) -> int:
        """Stored bytes of one token in ONE layer in the TWO pools the
        decode kernels walk (what decides their chunk: `narrow`)."""
        return sum(self.entry_widths) * jnp.dtype(self.dtype).itemsize

    def bytes_per_token(self):
        """Stored bytes of one token in ONE layer, EVERY entry: the two a
        token, and a rated entry's `width / stride` (an int where that is
        whole)."""
        lanes = sum(self.entry_widths) + sum(
            fractions.Fraction(int(w), int(s)) for w, s in self.rated)
        n = lanes * jnp.dtype(self.dtype).itemsize
        return int(n) if n == int(n) else float(n)

    def pool_bytes(self) -> int:
        """Device bytes of ALL the block pools' `num_blocks` blocks, the
        rated entries' too."""
        rated = sum(l * int(self.num_blocks) * lanes
                    for l, _, lanes in self.rated_pool_shapes)
        return (sum(math.prod(s) for s in self.pool_shapes) + rated) * \
            jnp.dtype(self.dtype).itemsize


def run_chunks(blocks: Sequence[int], per_chunk: int) -> Tuple[int, int]:
    """(runs, chunks) of one sequence's table: its chunks of `per_chunk`
    entries (the last may be shorter), and how many of them hold
    consecutive block ids and nothing else. THE definition of "the paged
    decode kernels read this chunk with one copy a pool"
    (ops/pallas/paged_attention.py `_walk`: a full chunk of one run is one
    copy; the sequence's last, shorter chunk the binary pieces of its
    length, at most four of them at 16 blocks a chunk, five at 32)."""
    ids = np.asarray(blocks, np.int64)
    if not ids.size:
        return 0, 0
    chunks = -(-ids.size // per_chunk)
    breaks = np.flatnonzero(np.diff(ids) != 1) + 1
    # a break AT a chunk's first entry parts two chunks and splits neither
    split = np.unique(breaks[breaks % per_chunk != 0] // per_chunk)
    return chunks - split.size, chunks


class _FreeExtents:
    """The free block ids as extents `[start, end)`, no two of them
    adjacent: what lets `BlockAllocator` hand out RUNS. `_starts` is kept
    sorted; a take or a give touches the extents, not the ids (some tens
    under churn, against 9 216 blocks)."""

    def __init__(self, lo: int, hi: int):
        self.count = 0
        self._starts: List[int] = []
        self._end: Dict[int, int] = {}          # start -> end
        self._start_of_end: Dict[int, int] = {}
        self._add(lo, hi)

    def __len__(self) -> int:
        return self.count

    def _add(self, start: int, end: int) -> None:
        if start < end:
            bisect.insort(self._starts, start)
            self._end[start] = end
            self._start_of_end[end] = start
            self.count += end - start

    def _drop(self, start: int) -> int:
        """Forget the extent at `start`; its end."""
        end = self._end.pop(start)
        del self._start_of_end[end]
        del self._starts[bisect.bisect_left(self._starts, start)]
        self.count -= end - start
        return end

    def _carve(self, start: int, first: int, n: int) -> List[int]:
        """The ids `first .. first + n` out of the extent at `start`."""
        end = self._drop(start)
        self._add(start, first)
        self._add(first + n, end)
        return list(range(first, first + n))

    def _longest_first(self, start: int) -> Tuple[int, int]:
        """Sort key of an extent: the longest first (ties: the lowest)."""
        return start - self._end[start], start

    def take(self, n: int) -> List[int]:
        """n ids (the caller has checked that n are free): the first n of
        the LOWEST extent that holds n (address-ordered first fit); where
        none does, the largest extents whole, then the first ids of the
        next largest: the fewest pieces, each ascending, in address
        order."""
        if not n:
            return []
        for start in self._starts:
            if self._end[start] - start >= n:
                return self._carve(start, start, n)
        pieces = []
        for start in sorted(self._starts, key=self._longest_first):
            pieces.append((start, min(self._end[start] - start, n)))
            n -= pieces[-1][1]
            if not n:
                break
        return [b for start, k in sorted(pieces)
                for b in self._carve(start, start, k)]

    def take_after(self, last: int) -> int:
        """One id (the caller has checked that one is free) for the table
        that ends with `last`: `last + 1` if it is free (which for an
        owned `last` means it starts an extent), so that the run goes on;
        else the MIDDLE of the largest extent, where a new run has the
        most room before it meets another sequence's (the start of an
        extent is where the sequence before it grows, and where the next
        admission lands: against the lowest free block, `take(1)`, the
        chip read `run_chunk_share` 0.905 for 0.858 where tables are
        mostly prompt and 0.71 for 0.355 where they are mostly growth,
        and there the grouped-query walk at 53% of its roofline for 41%;
        PERF.md section 6, PR 40; tests/test_block_runs.py runs the same
        closed loops over the allocator alone and counts the same)."""
        if last + 1 in self._end:
            return self._carve(last + 1, last + 1, 1)[0]
        start = min(self._starts, key=self._longest_first)
        return self._carve(start, (start + self._end[start]) // 2, 1)[0]

    def give(self, block: int) -> None:
        start, end = block, block + 1
        if block in self._start_of_end:         # an extent ends here
            start = self._start_of_end[block]
            self._drop(start)
        if end in self._end:                    # and one starts after it
            end = self._drop(end)
        self._add(start, end)


class BlockAllocator:
    """Host-side allocator over the pool's block ids (1..num_blocks-1;
    block 0 is never handed out). Single-owner by design — the decode
    scheduler thread is the only caller — so no locking here.

    It hands out RUNS: `alloc(n)` returns n consecutive ascending ids
    wherever a free extent holds n (the lowest such extent; a fresh pool
    gives 1, 2, 3, ...), else the fewest ascending pieces, and `grow`
    extends a table by the block after its last one when that is free.
    Nothing is reserved ahead of need: a block is free or owned, so
    `can_alloc`, admission and preemption see the capacity they always
    saw. Any free block still serves any sequence; a run is what the paged
    decode kernels fetch with ONE copy a pool and chunk where a token stores
    little (ops/pallas/paged_attention.py `narrow`; a wide cache's walk
    stays a block a copy and the count then describes its tables alone),
    and `run_chunks` counts it.

    Accounting: `waste_fraction` is *internal* waste — slots allocated
    but not (yet) holding a live token — against the allocated capacity.
    `run_chunk_share` is the *external* side: of the chunks of the live
    tables, the share the kernels read with one copy a pool, kept as
    tables are built (`alloc`, `grow`) and given back (`free`: a table
    whole, as they built it)."""

    def __init__(self, cfg: KVCacheConfig):
        from ..ops.pallas.paged_attention import blocks_per_chunk

        self.cfg = cfg
        if cfg.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is reserved), got "
                f"{cfg.num_blocks}")
        self._free = _FreeExtents(1, cfg.num_blocks)
        self._owned: Dict[int, bool] = {}
        # blocks the kernels' walk takes a chunk of a table as wide as this
        # cache's: `run_chunks`' unit
        self.per_chunk = blocks_per_chunk(
            cfg.block_size, cfg.walk_bytes_per_token(),
            cfg.max_blocks_per_seq * cfg.block_size)
        self._runs = self._chunks = 0       # of the live tables

    def free_blocks(self) -> int:
        return len(self._free)

    def used_blocks(self) -> int:
        return len(self._owned)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        """A new table of n blocks, one ascending run where a free extent
        holds n; raises NoBlocksError without allocating anything when
        fewer than n are free (a partial grant would leak on the caller's
        error path)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            raise NoBlocksError(
                f"need {n} blocks, only {len(self._free)} of "
                f"{self.cfg.usable_blocks} free")
        out = self._free.take(n)
        for b in out:
            self._owned[b] = True
        runs, chunks = run_chunks(out, self.per_chunk)
        self._runs += runs
        self._chunks += chunks
        return out

    def grow(self, table: List[int]) -> None:
        """Append one block to a sequence's (non-empty) table: the block
        after its last one if that is free, so that the run goes on, else
        the middle of the largest free extent (`_FreeExtents.take_after`).
        Raises NoBlocksError, the table as it was, when none is free."""
        if not len(self._free):
            raise NoBlocksError(
                f"need 1 block, none of {self.cfg.usable_blocks} free")
        last = table[-1]
        block = self._free.take_after(last)
        self._owned[block] = True
        into = len(table) % self.per_chunk     # entries of its chunk so far
        if not into:
            self._runs += 1
            self._chunks += 1
        elif block != last + 1 and run_chunks(
                table[-into:], self.per_chunk)[0]:
            self._runs -= 1
        table.append(block)

    def free(self, blocks: Sequence[int]):
        """Return blocks to the pool. Double-free and foreign ids are
        programming errors and raise — silently re-listing a block
        would hand the same block to two sequences."""
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("block 0 (null block) is never "
                                 "allocated and cannot be freed")
            if b not in self._owned:
                raise ValueError(f"block {b} is not allocated "
                                 "(double free?)")
            del self._owned[b]
            self._free.give(int(b))
        runs, chunks = run_chunks(blocks, self.per_chunk)
        self._runs -= runs
        self._chunks -= chunks

    def stats(self, live_tokens: int = 0) -> Dict[str, float]:
        used = self.used_blocks()
        cap = used * self.cfg.block_size
        waste = max(0, cap - int(live_tokens))
        return {
            "blocks_total": self.cfg.usable_blocks,
            "blocks_free": self.free_blocks(),
            "blocks_used": used,
            "block_size": self.cfg.block_size,
            "live_tokens": int(live_tokens),
            "allocated_token_capacity": cap,
            "internal_waste_tokens": waste,
            "waste_fraction": round(waste / cap, 4) if cap else 0.0,
            # of the live tables' chunks, the share the paged decode
            # kernels read with one copy a pool (None: no live table)
            "run_chunk_share": round(self._runs / self._chunks, 4)
            if self._chunks > 0 else None,
            # tokens such a chunk holds: what the walk over this cache's
            # tables fetches a chunk (`paged_attention.chunk_tokens`)
            "walk_chunk_tokens": self.per_chunk * self.cfg.block_size,
            "pool_bytes": self.cfg.pool_bytes(),
            # what a token stores in a layer: lanes of the two entries
            # (the model's say) and their bytes
            "entry_widths": list(self.cfg.entry_widths),
            # entries stored at a rate beside them: lanes, stride in tokens
            # and bytes a token a layer of each
            "rated_entries": [
                {"width": int(w), "stride": int(s),
                 "bytes_per_token_layer":
                     w * jnp.dtype(self.cfg.dtype).itemsize / s}
                for w, s in self.cfg.rated],
            "bytes_per_token_layer": self.cfg.bytes_per_token(),
        }


class StateRowAllocator:
    """Host-side free-list over the rows of a model's STATE pools: what a
    sequence keeps that is not a token's (a recurrent layer's state, a
    fixed size a sequence whatever its length; `models/decoder.ServeModel
    .state_pools` gives the pools' shapes, `[layers of the kind, rows,
    ...]`). A sequence holds ONE row id, the same in every pool and layer,
    from admission to finish; the programs get the ids as they get block
    tables. Row 0 is the null row: idle decode slots read and write it,
    and it is never handed out. One row a slot of the largest decode
    configuration plus the null row, so an admission that found a slot
    finds a row. Single-owner like `BlockAllocator`: no locking."""

    def __init__(self, rows: int, pools=()):
        if rows < 2:
            raise ValueError(f"a state pool needs the null row and one "
                             f"more, got {rows} rows")
        self.rows = int(rows)
        self._free: List[int] = list(range(self.rows - 1, 0, -1))
        self._owned: Dict[int, bool] = {}
        self._bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                          for shape, dt in pools)

    def used_rows(self) -> int:
        return len(self._owned)

    def alloc(self) -> int:
        if not self._free:
            raise NoBlocksError(
                f"no state row free of {self.rows - 1}")
        row = self._free.pop()
        self._owned[row] = True
        return row

    def free(self, row: int):
        """Double-free and foreign ids raise, as a block's do: a row listed
        twice would hold two sequences' state."""
        if row == NULL_ROW:
            raise ValueError("row 0 (the null row) is never allocated "
                             "and cannot be freed")
        if row not in self._owned:
            raise ValueError(f"state row {row} is not allocated "
                             "(double free?)")
        del self._owned[row]
        self._free.append(int(row))

    def stats(self) -> Dict[str, int]:
        """`DecodeEngine.status()["state"]`: rows a sequence can hold (the
        null row left out), rows held, and the pools' device bytes."""
        return {"rows": self.rows - 1, "used": self.used_rows(),
                "bytes": int(self._bytes)}


# ---------------------------------------------------------------------------
# Pure pool helpers (traced into the decode/prefill executables). Their
# ops carry the layer scopes `kv_write` / `kv_gather` (HLO metadata: a
# profile's device time reduces by them, PERF.md section 3).
#
# Every helper takes the WHOLE pool `[L, NB, BS, *tok]` and a (traced)
# layer index, and addresses it in place by (layer, block, slot), or by
# (layer, block) where whole blocks are written: the layer loop of
# models/decoder.py holds the pools in its carry, so a write goes into
# the donated buffer (one scatter, or write_prefill_kv's copy a block)
# and no layer's slice is ever taken out or put back. `tok` =
# `pool.shape[3:]` is how one token is stored: `[width]` in the engine's
# pools (`KVCacheConfig.pool_shapes`); the helpers are generic over it and
# `kv`'s trailing dimensions match it. "K (or V)" below reads as "either
# entry".
# ---------------------------------------------------------------------------


def init_pools(cfg: KVCacheConfig) -> Tuple[jax.Array, jax.Array]:
    """The two zeroed pools, `cfg.pool_shapes`."""
    dt = jnp.dtype(cfg.dtype)
    a, b = cfg.pool_shapes
    return jnp.zeros(a, dt), jnp.zeros(b, dt)


def init_rated_pools(cfg: KVCacheConfig) -> Tuple[jax.Array, ...]:
    """The zeroed pools of the entries stored at a rate,
    `cfg.rated_pool_shapes`; () where the model stores none."""
    dt = jnp.dtype(cfg.dtype)
    return tuple(jnp.zeros(shape, dt) for shape in cfg.rated_pool_shapes)


@jax.named_scope("kv_write")
def write_token_kv(pool: jax.Array, layer: jax.Array, kv: jax.Array,
                   block_tables: jax.Array, positions: jax.Array,
                   block_size: int) -> jax.Array:
    """Scatter one token's K (or V) per slot into layer `layer` of the
    pool. pool `[L, NB, BS, *tok]`, kv `[S, *tok]`, block_tables
    `[S, MB]`, positions `[S]`. Inactive slots carry all-zero tables,
    so their writes land in the null block."""
    blk = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    slot = positions % block_size
    return pool.at[layer, blk, slot].set(kv)


@jax.named_scope("kv_write")
def write_prefill_kv(pool: jax.Array, layer: jax.Array, kv: jax.Array,
                     block_table: jax.Array, block_size: int) -> jax.Array:
    """Write a whole prompt's K (or V) into layer `layer` of the pool.
    pool `[L, NB, BS, *tok]`, kv `[T, *tok]` (positions 0..T-1),
    block_table `[MB]`.

    The unit is chosen by the static shape: a `T` of whole blocks goes in
    a block at a time, `kv` viewed as `[T/BS, BS, *tok]` to
    `pool[layer, block_table[:T/BS]]`: `T/BS` updates of one contiguous
    block each (on a TPU one DMA a block, ops/pallas/kv_block_write.py;
    elsewhere one scatter with a block a window). A `T` that is not (the
    engine's smallest default bucket is 8, under a block) keeps the row
    form, one update a token. `PREFILL_WRITE_UNITS` counts which.

    Either way: table entries past the sequence's allocated blocks are
    still 0, so those positions land in the null block (several updates
    may name it; any order is right, nothing reads it unmasked);
    positions inside the last allocated block but past the true length
    write garbage slots that the decode step overwrites before any mask
    ever lets them be read."""
    from ..ops.pallas import kv_block_write as bw

    T = kv.shape[0]
    whole = T % block_size == 0
    PREFILL_WRITE_UNITS["blocks" if whole else "rows"] += 1
    if whole:
        nb = T // block_size
        kv = kv.reshape(nb, block_size, *kv.shape[1:])
        if bw.use_dma(kv, pool):
            return bw.write_blocks(pool, layer, kv, block_table[:nb])
        return pool.at[layer, block_table[:nb]].set(kv)
    t = jnp.arange(T, dtype=jnp.int32)
    return pool.at[layer, block_table[t // block_size],
                   t % block_size].set(kv)


@jax.named_scope("kv_write")
def write_chunk_kv(pool: jax.Array, layer: jax.Array, kv: jax.Array,
                   block_table: jax.Array, start: jax.Array,
                   block_size: int) -> jax.Array:
    """Scatter one prompt SLICE's K (or V) into layer `layer` of the
    pool (chunked prefill). pool `[L, NB, BS, *tok]`, kv `[C, *tok]`
    holding positions start..start+C-1, block_table `[MB]`. Positions
    past the table width are redirected to the null block (the final
    chunk's edge-padded tail can run past max_len); positions inside
    allocated blocks but past the true prompt length write garbage
    slots that later writes overwrite before any mask lets them be
    read — the same contract as write_prefill_kv."""
    t = jnp.arange(kv.shape[0], dtype=jnp.int32) + start
    bi = t // block_size
    mb = block_table.shape[0]
    blk = jnp.where(bi < mb, block_table[jnp.minimum(bi, mb - 1)],
                    NULL_BLOCK)
    return pool.at[layer, blk, t % block_size].set(kv)


@jax.named_scope("kv_write")
def write_span_kv(pool: jax.Array, layer: jax.Array, kv: jax.Array,
                  block_tables: jax.Array, positions: jax.Array,
                  block_size: int) -> jax.Array:
    """Scatter a W-token span per slot into layer `layer` of the pool
    (speculative verification). pool `[L, NB, BS, *tok]`, kv
    `[S, W, *tok]` holding each slot's positions p..p+W-1, block_tables
    `[S, MB]`, positions `[S]` = each slot's span start. Slots with
    all-zero tables (inactive / masked out) write the null block; span
    positions past the table width are redirected there too."""
    w = kv.shape[1]
    t = positions[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    bi = t // block_size
    mb = block_tables.shape[1]
    blk = jnp.take_along_axis(block_tables, jnp.minimum(bi, mb - 1),
                              axis=1)
    blk = jnp.where(bi < mb, blk, NULL_BLOCK)
    return pool.at[layer, blk, t % block_size].set(kv)


@jax.named_scope("kv_gather")
def gather_kv(pool: jax.Array, layer: jax.Array,
              block_tables: jax.Array) -> jax.Array:
    """Gather every slot's full (padded) context from layer `layer` of
    the pool: `[L, NB, BS, *tok]` × `[S, MB]` → `[S, MB*BS, *tok]`. The
    caller masks positions `> position` (unwritten tail + null-block
    reads of inactive slots) and views `tok` as heads."""
    s, mb = block_tables.shape
    ctx = pool[layer, block_tables]                  # [S, MB, BS, *tok]
    return ctx.reshape(s, mb * pool.shape[2], *pool.shape[3:])


def write_token_rated(pool: jax.Array, layer: jax.Array, entry: jax.Array,
                      block_tables: jax.Array, index: jax.Array,
                      due: jax.Array, per_block: int) -> jax.Array:
    """Put one rated entry a slot into layer `layer` of its pool `[L, NB,
    per_block * width]`: entry `[S, width]` becomes the sequence's entry
    number `index` `[S]` (block `index // per_block` of its table, lanes
    `index % per_block` of that block's row) where `due` `[S]` is true; a
    slot that is not due writes the null block. The block's row is read,
    its `width` lanes replaced and the row written back: the pool keeps
    its shape, whole tiles whatever `per_block` is."""
    width = entry.shape[-1]
    blk = jnp.take_along_axis(
        block_tables, (index // per_block)[:, None], axis=1)[:, 0]
    blk = jnp.where(due, blk, NULL_BLOCK)
    row = pool[layer, blk]                                  # [S, E * width]
    at = jnp.arange(per_block * width, dtype=jnp.int32) // width
    row = jnp.where(at[None, :] == (index % per_block)[:, None],
                    jnp.tile(entry.astype(pool.dtype), (1, per_block)), row)
    return pool.at[layer, blk].set(row)


def write_blocks_rated(pool: jax.Array, layer: jax.Array,
                       entries: jax.Array, blocks: jax.Array) -> jax.Array:
    """Whole blocks of rated entries into layer `layer` of their pool:
    entries `[n * per_block, width]`, the entries of `n` consecutive blocks
    of a sequence, to the pool rows `blocks` `[n]` (block ids)."""
    n = blocks.shape[0]
    return pool.at[layer, blocks].set(
        entries.reshape(n, -1).astype(pool.dtype))


def gather_rated(pool: jax.Array, layer: jax.Array,
                 block_tables: jax.Array) -> jax.Array:
    """Every slot's rated entries through its table: `[L, NB, E * width]` x
    `[S, MB]` -> `[S, MB, E * width]`, a block's `E` entries side by side
    in the lanes as they lie (a reader slices the lanes; no re-layout).
    The caller's scope names it: `kv_gather` where a program gathers a
    whole context, the model's own (`select`) where it reads them to pick
    blocks."""
    return pool[layer, block_tables]


def ring_blocks(window: int, prompt_slice: int, block_size: int) -> int:
    """Blocks of a window-kind sequence's RING: the window and a prompt's
    slice, and one more. A slice's K/V are written before its first query
    reads back `window - 1` keys, so the ring holds both; the block more
    keeps a bucket's padded tail (up to a slice past the newest token) off
    every key a later step still reads, whatever the position's place in
    its block."""
    return (int(window) + int(prompt_slice)) // int(block_size) + 1


def window_table(blocks: Sequence[int], ring: int, max_blocks: int
                 ) -> np.ndarray:
    """Host helper: a window-kind sequence's table row. Under `ring`
    blocks it is `build_block_table`'s (the sequence holds its own length);
    a full ring is REPEATED over the table's width, `row[j] = blocks[j mod
    ring]`, so position `p` lives in block `p // BS` of the row as in any
    table, and the block that held position `p - ring * BS` now holds it."""
    n = len(blocks)
    if n > ring:
        raise ValueError(f"{n} blocks exceed the ring of {ring}")
    if n < ring:
        return build_block_table(blocks, max_blocks)
    reps = -(-max_blocks // ring)
    return np.tile(np.asarray(list(blocks), np.int32), reps)[:max_blocks]


def build_block_table(blocks: Sequence[int], max_blocks: int) -> np.ndarray:
    """Host helper: a sequence's padded table row (unused tail = null
    block)."""
    row = np.zeros((max_blocks,), np.int32)
    n = len(blocks)
    if n > max_blocks:
        raise ValueError(f"{n} blocks exceed table width {max_blocks}")
    row[:n] = np.asarray(list(blocks), np.int32)
    return row
