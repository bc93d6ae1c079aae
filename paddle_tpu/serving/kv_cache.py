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

Layering: this module owns the host-side `BlockAllocator` (free-list,
alloc/free, fragmentation accounting) and the pure jnp pool helpers
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

Block 0 is reserved as the *null block*: padded/inactive decode slots
and out-of-range table entries all read and write it, so a fixed-shape
executable needs no validity branches — the attention length mask
already guarantees nothing read from the null block ever contributes.

Beside the block pools a model with recurrent layers keeps STATE ROW
pools (`StateRowAllocator`): a fixed-size row a sequence, allocated at
admission, overwritten by its prefill, advanced in place by every decode
step, freed at finish, cancel and preemption (a replay's prefill rebuilds
it). Row 0 is the null row, as block 0 is the null block.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KVCacheConfig", "BlockAllocator", "NoBlocksError",
           "StateRowAllocator", "NULL_ROW",
           "init_pools", "write_token_kv", "write_prefill_kv",
           "write_chunk_kv", "write_span_kv", "gather_kv",
           "NULL_BLOCK", "PREFILL_WRITE_UNITS"]

NULL_BLOCK = 0
NULL_ROW = 0

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
    HBM and every helper and kernel here (a block = whole tiles, one DMA a
    block) serves both pools as it is; one padded row of 640 lanes costs
    the same bytes and would want a single-pool engine beside this one."""

    layers: int
    max_len: int
    block_size: int = 16
    num_blocks: int = 64
    dtype: str = "bfloat16"
    widths: Optional[Tuple[int, int]] = None
    kv_heads: int = 0
    head_dim: int = 0

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

    def bytes_per_token(self) -> int:
        """Stored bytes of one token in ONE layer, both entries."""
        return sum(self.entry_widths) * jnp.dtype(self.dtype).itemsize

    def pool_bytes(self) -> int:
        """Device bytes of BOTH pools."""
        return sum(math.prod(s) for s in self.pool_shapes) * \
            jnp.dtype(self.dtype).itemsize


class BlockAllocator:
    """Host-side free-list over the pool's block ids (1..num_blocks-1;
    block 0 is never handed out). Single-owner by design — the decode
    scheduler thread is the only caller — so no locking here.

    Fragmentation accounting: paged allocation has no *external*
    fragmentation (any free block serves any sequence), so the number
    reported is *internal* waste — slots allocated but not (yet)
    holding a live token — which `waste_fraction` reports against the
    allocated capacity."""

    def __init__(self, cfg: KVCacheConfig):
        self.cfg = cfg
        if cfg.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is reserved), got "
                f"{cfg.num_blocks}")
        self._free: List[int] = list(range(cfg.num_blocks - 1, 0, -1))
        self._owned: Dict[int, bool] = {}

    def free_blocks(self) -> int:
        return len(self._free)

    def used_blocks(self) -> int:
        return len(self._owned)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take n blocks off the free list; raises NoBlocksError
        without allocating anything when fewer than n are free (a
        partial grant would leak on the caller's error path)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            raise NoBlocksError(
                f"need {n} blocks, only {len(self._free)} of "
                f"{self.cfg.usable_blocks} free")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._owned[b] = True
        return out

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
            self._free.append(int(b))

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
            "pool_bytes": self.cfg.pool_bytes(),
            # what a token stores in a layer: lanes of the two entries
            # (the model's say) and their bytes
            "entry_widths": list(self.cfg.entry_widths),
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


def build_block_table(blocks: Sequence[int], max_blocks: int) -> np.ndarray:
    """Host helper: a sequence's padded table row (unused tail = null
    block)."""
    row = np.zeros((max_blocks,), np.int32)
    n = len(blocks)
    if n > max_blocks:
        raise ValueError(f"{n} blocks exceed table width {max_blocks}")
    row[:n] = np.asarray(list(blocks), np.int32)
    return row
