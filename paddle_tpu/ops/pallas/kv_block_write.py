"""A prompt's K (or V) into the paged KV pool, one DMA a block.

A whole-prompt prefill produces positions 0..T-1 of one sequence, which
fill that sequence's blocks in order, and the pool `[L, NB, BS, H*D]`
(serving/kv_cache.py) stores a block as one contiguous, unpadded run of
tiles. So the unit to write is the block: `kv` viewed as `[T/BS, BS, H*D]`
goes to `pool[layer, table[i]]`, `T/BS` copies of `BS * H*D` elements from
HBM to HBM, all in flight at once, into the pool where it lies (the
operand is aliased to the result; nothing of the pool's size is planned).
Nothing passes through VMEM and nothing is computed.

Why a kernel. The TPU carries a scatter's updates out one after another,
so its cost is their count: with one index a TOKEN a 1024-token prompt is
1024 updates of one 2560-byte row, 20 times over what the bytes take;
with one index a BLOCK XLA's scatter is ten times faster than that and
still half the speed of these copies, which run at the bytes (PERF.md
section 6, PR 30, has the three timed side by side on the chip). The
block scatter is the route everywhere this kernel does not run.

Two routes, one gate, as in `paged_attention.py`: on a TPU, at a pool
whose blocks are whole tiles, the kernel; everywhere else
`kv_cache.write_prefill_kv` keeps the scatter. The pick is final.

Several table entries may name the null block (a bucket longer than the
sequence's allocation): their copies land on one another in no order,
which is the null block's contract: nothing reads it unmasked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention


def use_dma(kv_blocks: jax.Array, pool: jax.Array) -> bool:
    """Whether the block write takes the kernel: the computation runs on a
    TPU, outside a mesh that would have to partition a Mosaic kernel, and
    a block `[BS, H*D]` of the pool is whole tiles of `kv_blocks`' dtype."""
    if pool.ndim != 4 or kv_blocks.dtype != pool.dtype \
            or pool.dtype.itemsize not in (2, 4):
        return False
    bs, hd = pool.shape[2:]
    return (_attention._platform(kv_blocks) == "tpu"
            and _attention._mesh_partitionable(kv_blocks)
            and kv_blocks.shape[1:] == (bs, hd) and hd % 128 == 0
            and bs % (32 // pool.dtype.itemsize) == 0)


def _kernel(layer_ref, blocks_ref, kv_hbm, pool_in, pool_out, sem):
    del pool_in                 # the same buffer as pool_out
    layer = layer_ref[0]

    def each_copy(act):
        def one(i, carry):
            getattr(pltpu.make_async_copy(
                kv_hbm.at[i], pool_out.at[layer, blocks_ref[i]], sem), act)()
            return carry

        lax.fori_loop(0, kv_hbm.shape[0], one, 0)

    each_copy("start")
    each_copy("wait")


def write_blocks(pool: jax.Array, layer: jax.Array, kv_blocks: jax.Array,
                 blocks: jax.Array, *, interpret: bool = False) -> jax.Array:
    """`pool[layer, blocks[i]] = kv_blocks[i]` for every i, in place: pool
    `[L, NB, BS, H*D]`, kv_blocks `[n, BS, H*D]` in the pool's dtype,
    blocks `[n]` (ids inside the pool). Returns the pool, which is the
    operand's buffer. `interpret` runs the kernel in the Pallas TPU
    interpreter (tests, off the chip)."""
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands count the two scalar-prefetch arguments: 3 is the pool
        input_output_aliases={3: 0},
        interpret=interpret,
        name="kv_block_write",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), blocks.astype(jnp.int32),
      kv_blocks, pool)
