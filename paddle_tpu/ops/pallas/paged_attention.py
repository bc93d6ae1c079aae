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

# which route each decode program's trace took ("paged" | "gather"), as
# attention's counts; DecodeEngine.status() reports them
GATE_COUNTS: collections.Counter = collections.Counter()

# tokens a compute step: a multiple of 128, so scores [M, _CHUNK] fill
# their lanes; K and V double-buffered are 4 * _CHUNK * H*D elements of VMEM
_CHUNK = 256

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def use_paged(q: jax.Array, pool: jax.Array, heads: int) -> bool:
    """Whether decode attention takes the kernel: the computation runs on
    a TPU, outside a mesh that would have to partition a Mosaic kernel,
    over a pool `[L, NB, BS, H*D]` whose blocks are whole tiles."""
    if pool.ndim != 4 or q.dtype != pool.dtype \
            or pool.dtype.itemsize not in (2, 4):
        return False
    bs, hd = pool.shape[2:]
    head_dim = hd // heads
    return (_attention._platform(q) == "tpu"
            and _attention._mesh_partitionable(q)
            and heads * head_dim == hd and hd % 128 == 0
            and (head_dim == 64 or head_dim % 128 == 0)
            and bs % (32 // pool.dtype.itemsize) == 0
            and _CHUNK % bs == 0)


def _kernel(layer_ref, tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, done_ref, *, heads: int, scale: float):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    _, chunk, hd = kbuf.shape
    bs = k_hbm.shape[2]
    per_chunk = chunk // bs
    max_blocks = tables_ref.shape[1]
    head_dim = hd // heads
    m_rows = -(-heads // 16) * 16
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
            for which, (hbm, dst) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, blk], dst.at[buf, rows],
                    sems.at[buf, which]), act)()
            return carry

        lax.fori_loop(0, n, one, 0)

    @pl.when(s == 0)
    def _():
        done_ref[0] = 0
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        each_copy(0, 0, 0, "start")

    def start_next_slot(buf):
        @pl.when(s + 1 < n_slots)
        def _():
            each_copy(s + 1, 0, buf, "start")

    done = done_ref[0]          # chunks consumed so far: the buffers' turn
    chunks = (live_blocks(s) + per_chunk - 1) // per_chunk
    pos = pos_ref[s]

    row = lax.broadcasted_iota(jnp.int32, (m_rows, hd), 0)
    col = lax.broadcasted_iota(jnp.int32, (m_rows, hd), 1)
    own = (col >= row * head_dim) & (col < (row + 1) * head_dim)
    q = jnp.broadcast_to(q_ref[...].astype(jnp.float32), (m_rows, hd))
    q = jnp.where(own, q, 0.0).astype(kbuf.dtype)

    @pl.when(chunks == 0)
    def _():
        start_next_slot(done % 2)

    def consume(c, carry):
        m, l, acc = carry
        buf = (done + c) % 2

        @pl.when(c + 1 < chunks)
        def _():
            each_copy(s, c + 1, 1 - buf, "start")

        @pl.when(c + 1 == chunks)
        def _():
            start_next_slot(1 - buf)

        each_copy(s, c, buf, "wait")
        k = kbuf[buf]
        v = vbuf[buf]
        sc = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        tok = c * chunk + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(tok <= pos, sc, _MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, chunks, consume,
        (jnp.full((m_rows, 1), _MASKED, jnp.float32),
         jnp.zeros((m_rows, 1), jnp.float32),
         jnp.zeros((m_rows, hd), jnp.float32)))
    done_ref[0] = done + chunks
    # an inactive slot (l == 0) gives zeros; its row is never read
    ctx = jnp.where(own, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[...] = jnp.sum(ctx, axis=0, keepdims=True)


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
            scratch_shapes=[
                pltpu.VMEM((2, _CHUNK, hd), k_pool.dtype),
                pltpu.VMEM((2, _CHUNK, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables.astype(jnp.int32), positions.astype(jnp.int32),
      q[:, None, :], k_pool, v_pool)
    return out[:, 0, :].astype(q.dtype)
