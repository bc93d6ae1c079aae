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
the null block is inactive and reads nothing. Blocks are fetched `_CHUNK //
BS` at a time into one of two VMEM buffers, one DMA a block for K and one
for V; while a chunk is consumed the next is in flight, and the last chunk
of a slot overlaps the first of the next slot. How a slot's context is
chunked depends on its own length alone, so a row's result does not depend
on what shares the batch (`ServeModel`'s contract). Rows of a buffer past
the live blocks hold what an earlier chunk left there: their scores are
masked, and their weights are exactly zero against V rows that are finite
(the buffers start zeroed; the pool's garbage is finite by the same
contract the gather path relies on).
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

# which route each decode program's trace took ("paged" | "paged_gqa" |
# "paged_latent" | "gather"), as attention's counts; DecodeEngine.status()
# reports them
GATE_COUNTS: collections.Counter = collections.Counter()

# tokens a compute step: a multiple of 128, so scores [M, _CHUNK] fill
# their lanes; K and V double-buffered are 4 * _CHUNK * H*D elements of VMEM
_CHUNK = 256

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _tiles(pool: jax.Array) -> bool:
    """Whether a pool `[L, NB, BS, width]` is one the kernels can address:
    a block is whole tiles, fetched `_CHUNK // BS` to a chunk."""
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
    D whole lane tiles and the query heads whole sublane tiles."""
    if not _tiles(pool) or q.dtype != pool.dtype:
        return False
    head_dim = pool.shape[3] // kv_heads
    return (_on_one_tpu(q) and kv_heads * head_dim == pool.shape[3]
            and heads % kv_heads == 0 and head_dim % 128 == 0
            and heads % (32 // pool.dtype.itemsize) == 0)


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


def _walk(layer_ref, tables_ref, pos_ref, pools, bufs, sems, done_ref,
          zeroed, setup, step):
    """One grid step's walk over slot `program_id(0)`'s live blocks of
    layer `layer_ref[0]`: chunk by chunk through the double buffers `bufs`
    (one `[2, _CHUNK, width]` a pool, `sems` `[2, len(pools)]`), one DMA a
    block and pool, the next chunk (or the next slot's first) in flight
    while `step(query, c, buf, carry) -> carry` consumes chunk `c` from
    `bufs[i][buf]`. `setup() -> (query, first carry)` builds the slot's
    query operands; it runs AFTER the call's first copies are started, so
    that no DMA waits for it. `zeroed` are the buffers whose stale rows
    meet exact zero weights and so must be finite from the start. Returns
    (query, the last carry: the first, for a slot that reads nothing)."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    chunk = bufs[0].shape[1]
    bs = pools[0].shape[2]
    per_chunk = chunk // bs
    max_blocks = tables_ref.shape[1]
    layer = layer_ref[0]

    def live_blocks(slot):
        live = jnp.minimum(pos_ref[slot] // bs + 1, max_blocks)
        return jnp.where(tables_ref[slot, 0] == 0, 0, live)

    def each_copy(slot, c, buf, act):
        """`act` ("start" | "wait") on the copy of every live block of
        chunk `c` of `slot` into buffer `buf`."""
        first = c * per_chunk
        n = jnp.clip(live_blocks(slot) - first, 0, per_chunk)

        def one(j, carry):
            blk = tables_ref[slot, first + j]
            rows = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for which, (hbm, dst) in enumerate(zip(pools, bufs)):
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, blk], dst.at[buf, rows],
                    sems.at[buf, which]), act)()
            return carry

        lax.fori_loop(0, n, one, 0)

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
    chunks = (live_blocks(s) + per_chunk - 1) // per_chunk
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


def _softmax_step(sc, vals, c, pos, carry):
    """One chunk of the online softmax: scores `sc` [M, chunk] (float32,
    scaled) of the tokens `c * chunk ...`, of which those `<= pos` count,
    against `vals` [chunk, width]."""
    m, l, acc = carry
    tok = c * sc.shape[1] + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    sc = jnp.where(tok <= pos, sc, _MASKED)
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


def _kernel(layer_ref, tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, done_ref, *, heads: int, scale: float):
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
        sc = lax.dot_general(q, kbuf[buf], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        return _softmax_step(sc, vbuf[buf], c, pos, carry)

    (_, _, own), (m, l, acc) = _walk(
        layer_ref, tables_ref, pos_ref, (k_hbm, v_hbm), (kbuf, vbuf), sems,
        done_ref, (vbuf,), setup, step)
    # an inactive slot (l == 0) gives zeros; its row is never read
    ctx = jnp.where(own, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[...] = jnp.sum(ctx, axis=0, keepdims=True)


def _gqa_kernel(layer_ref, tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, sems, done_ref, *, kv_heads: int, scale: float):
    heads, head_dim = q_ref.shape
    width = kbuf.shape[2]               # kv_heads * head_dim
    group = heads // kv_heads

    def setup():
        pos = pos_ref[pl.program_id(0)]
        row = lax.broadcasted_iota(jnp.int32, (heads, width), 0)
        col = lax.broadcasted_iota(jnp.int32, (heads, width), 1)
        own = col // head_dim == row // group
        q = jnp.concatenate([q_ref[...]] * kv_heads, axis=1)
        q = jnp.where(own, q, jnp.zeros_like(q))
        return (pos, q, own), _softmax_init(heads, width)

    def step(query, c, buf, carry):
        pos, q, _ = query
        sc = lax.dot_general(q, kbuf[buf], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        return _softmax_step(sc, vbuf[buf], c, pos, carry)

    (_, _, own), (m, l, acc) = _walk(
        layer_ref, tables_ref, pos_ref, (k_hbm, v_hbm), (kbuf, vbuf), sems,
        done_ref, (vbuf,), setup, step)
    # row h keeps its own K/V head's lanes; an inactive slot gives zeros
    ctx = jnp.where(own, acc / jnp.where(l > 0, l, 1.0), 0.0)
    out = ctx[:, :head_dim]
    for g in range(1, kv_heads):
        out = out + ctx[:, g * head_dim:(g + 1) * head_dim]
    o_ref[...] = out.astype(o_ref.dtype)


def _latent_kernel(layer_ref, tables_ref, pos_ref, ql_ref, qr_ref, c_hbm,
                   r_hbm, o_ref, cbuf, rbuf, sems, done_ref, *,
                   scale: float):
    last = (((1,), (1,)), ((), ()))

    def setup():
        # [heads, latent] with W_UK in it already, [heads, rotary lanes]
        return (pos_ref[pl.program_id(0)], ql_ref[...], qr_ref[...]), \
            _softmax_init(*ql_ref.shape)

    def step(query, c, buf, carry):
        pos, ql, qr = query
        ctx = cbuf[buf]         # keys AND values: one row a token, all heads
        sc = (lax.dot_general(ql, ctx, last,
                              preferred_element_type=jnp.float32)
              + lax.dot_general(qr, rbuf[buf], last,
                                preferred_element_type=jnp.float32)) * scale
        return _softmax_step(sc, ctx, c, pos, carry)

    # rbuf meets the mask alone; cbuf's stale rows meet zero weights
    _, (m, l, acc) = _walk(layer_ref, tables_ref, pos_ref, (c_hbm, r_hbm),
                           (cbuf, rbuf), sems, done_ref, (cbuf,), setup,
                           step)
    o_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _scalars(layer, block_tables, positions):
    return (jnp.reshape(layer, (1,)).astype(jnp.int32),
            block_tables.astype(jnp.int32), positions.astype(jnp.int32))


def _scratch(k_pool, v_pool):
    return [pltpu.VMEM((2, _CHUNK, k_pool.shape[3]), k_pool.dtype),
            pltpu.VMEM((2, _CHUNK, v_pool.shape[3]), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32)]


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    layer: jax.Array, block_tables: jax.Array,
                    positions: jax.Array, *, heads: int,
                    interpret: bool = False) -> jax.Array:
    """q `[S, H*D]` against layer `layer` of the pools `[L, NB, BS, H*D]`
    through block_tables `[S, MB]`: slot s attends key positions
    `0..positions[s]`, scaled by `1/sqrt(D)`, scores and softmax in
    float32, and gets its context `[H*D]` in q's dtype; a slot whose
    table starts with the null block gets zeros. `interpret` runs the
    kernel in the Pallas TPU interpreter (tests, off the chip)."""
    n_slots, hd = q.shape
    kernel = functools.partial(_kernel, heads=heads,
                               scale=1.0 / math.sqrt(hd // heads))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, 1, hd), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, 1, hd), lambda s, *_: (s, 0, 0)),
            scratch_shapes=_scratch(k_pool, v_pool)),
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(*_scalars(layer, block_tables, positions), q[:, None, :], k_pool,
      v_pool)
    return out[:, 0, :].astype(q.dtype)


def paged_gqa_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        layer: jax.Array, block_tables: jax.Array,
                        positions: jax.Array, *, heads: int, kv_heads: int,
                        interpret: bool = False) -> jax.Array:
    """Grouped-query decode attention: q `[S, heads*D]` against layer
    `layer` of the pools `[L, NB, BS, kv_heads*D]` through block_tables
    `[S, MB]`; query head h reads K/V head `h // (heads / kv_heads)`. Slot
    s attends key positions `0..positions[s]` at `1/sqrt(D)`, scores and
    softmax in float32, and gets `[heads*D]` in q's dtype; a slot whose
    table starts with the null block gets zeros."""
    n_slots = q.shape[0]
    head_dim = k_pool.shape[3] // kv_heads
    per_slot = lambda s, *_: (s, 0, 0)      # noqa: E731
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, kv_heads=kv_heads,
                          scale=1.0 / math.sqrt(head_dim)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, heads, head_dim), per_slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, head_dim), per_slot),
            scratch_shapes=_scratch(k_pool, v_pool)),
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_gqa_attention",
    )(*_scalars(layer, block_tables, positions),
      q.reshape(n_slots, heads, head_dim), k_pool, v_pool)
    return out.reshape(n_slots, heads * head_dim)


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
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((None, heads, latent), per_slot),
                pl.BlockSpec((None, heads, rope), per_slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, latent), per_slot),
            scratch_shapes=_scratch(c_pool, r_pool)),
        out_shape=jax.ShapeDtypeStruct(q_latent.shape, q_latent.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(*_scalars(layer, block_tables, positions), q_latent, q_rope, c_pool,
      r_pool)
