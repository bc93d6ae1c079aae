"""The decode step's state-space update, in place in the state row pool,
for the two recurrences of `ops/ssm.py`: `state_update` / `use_kernel` the
one whose decay is ONE SCALAR A HEAD (Mamba-2, lightning attention:
`ssd_step`), `selective_update` / `use_selective_kernel` the one whose
decay differs a channel AND a state lane (Mamba-1: `selective_step`; its
section is at the end of this docstring). Both count their route in
`GATE_COUNTS`.

A decode step advances one token a slot: every slot's SSM state `[H, P,
N]` float32 (2 MB at 64 x 64 x 128) is read, decayed, added to and read
out once, `ops/ssm.ssd_step`. Through XLA that is a gather of the slots'
rows out of the pool `[layers, rows, H, P, N]`, the update on the copy and
a scatter back: the rows cross HBM five times where twice is the least
(tests/test_tpu_aot_compile.py holds the program's HLO to it). This kernel
takes the WHOLE pool, aliased to its own output, the layer index and the
slots' row ids, and walks (slot, block of heads): the pipeline fetches
`pool[layer, rows[s], heads]` into VMEM, the body updates it and reads it
out, and the block goes back to where it came from. Nothing else of the
pool moves.

Two routes, one gate, as in `paged_attention.py`: on one TPU, at shapes
whose blocks are whole tiles, the kernel; everywhere else the caller keeps
the gathered form. The pick is final.

How the body stays on whole tiles, and off the MXU. A head's state is a
`[P, N]` tile stack with N in the lanes. `S' = decay S + (dt x) B^T` needs
`dt x` [P] down the sublanes and broadcast along the lanes, but every
activation arrives as a row (P in the lanes), and a column of one lane
would be padded to 128 in HBM and cost as much as the state. So the row
operands come TRANSPOSED, `[P, H]` a slot (P in the sublanes, a head a
lane: 32 KB, a sixtieth of the state): head h's column is picked by a lane
mask and a lane sum, broadcast along the lanes and multiplied by B's row
broadcast down the sublanes: the outer product on the VPU, exact in
float32. `y = S' C` is a product with C's row and a lane sum, which leaves
y as a column again; the columns are gathered into `[P, H]` by the same
mask. The per-head decay is a scalar out of SMEM. A first form put the outer
product through the MXU (`diag(dt x) . B` at the highest precision, and
`C . S'^T`): 6 passes and a transposed tile a head, 0.31 us a head where
the head's 64 KB move in 0.08 (chip run of PR 34: 28% of the roofline).

Idle slots carry row 0, several of them: their blocks are read and written
in the grid's order, stale or not, and nothing reads row 0 for a live
sequence.

The SELECTIVE recurrence's rows (`selective_update`). A row is `[N,
channels]` float32 with the channels in the lanes (16 x 5120: 2 x 40 whole
tiles, one contiguous 320 KB); `S' = exp(dt A) S + (dt x) B` is elementwise
in it, with `dt` and `dt x` rows `[1, channels]` broadcast down the
sublanes and B and C columns `[N, 1]` broadcast along the lanes, and `y =
sum_n S' C` a sum over the 16 sublanes. The decay is a value a state VALUE,
so it is computed IN the kernel from `dt` and `A` `[N, channels]` (a
block of its own that never changes and is fetched once a call): handing
`exp(dt A)` in would double the bytes. The grid walks the slots, a whole
row a step. The rows `dt`, `dt x` and `y` are `[slots, channels]` arrays
taken eight slots a block (one sublane tile; a slot alone would be padded
to a tile in HBM and cost half a state row) and a slot's row picked by its
index in the tile; B and C arrive as `[slots, N, 1]`, whose padding to a
lane tile is 8 KB a slot, a fortieth of the row. The body goes through the
row in pieces of `_LANES` channels so that a piece's values stay in
registers.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

# which route each traced state update took ("kernel" | "xla"), a count a
# recurrent layer of a decode program; DecodeEngine.status() reports them
GATE_COUNTS: collections.Counter = collections.Counter()

# bytes of one block of the walk: a head's [P, N] tiles, as many heads as
# fit (two in and two out buffers are live at once: 4 MB of VMEM). Chip run
# of PR 34, 64 rows x 4 layers: 632 GB/s at 1 MB, 635 at 2 MB, 571 at 512 KB
_BLOCK_BYTES = 1 << 20


def _heads_per_block(pool) -> int:
    H, P, N = pool.shape[2:]
    hb = max(1, min(H, _BLOCK_BYTES // (P * N * pool.dtype.itemsize)))
    while H % hb:
        hb -= 1
    return hb


def use_kernel(x: jax.Array, pool: jax.Array, groups: int) -> bool:
    """Whether the state update takes the kernel: on one TPU, over a
    float32 pool `[L, R, H, P, N]` whose heads are whole `[P, N]` tiles, a
    block of heads whole sublane tiles of the row operands and whole
    groups."""
    if pool.ndim != 5 or pool.dtype != jnp.float32:
        return False
    H, P, N = pool.shape[2:]
    hb = _heads_per_block(pool)
    per_group = H // groups
    return (_pa._on_one_tpu(x) and H % groups == 0 and P % 8 == 0
            and N % 128 == 0 and hb % per_group == 0)


def _kernel(layer_ref, rows_ref, decay_ref, dtx_ref, b_ref, c_ref, pool_ref,
            y_ref, out_ref, *, per_group: int):
    s, j = pl.program_id(0), pl.program_id(1)
    hb, P, N = pool_ref.shape
    H = dtx_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (P, H), 1)
    dtx = dtx_ref[...]                                          # [P, H]

    def head(h, ys):
        at = j * hb + h
        g = at // per_group
        mine = lane == at
        col = jnp.sum(jnp.where(mine, dtx, 0.0), axis=1, keepdims=True)
        new = decay_ref[s, at] * pool_ref[h] \
            + col * b_ref[pl.ds(g, 1), :]                       # [P, N]
        out_ref[h] = new
        y = jnp.sum(new * c_ref[pl.ds(g, 1), :], axis=1, keepdims=True)
        return jnp.where(mine, y, ys)

    # several heads a trip, so that one head's loads and lane sums overlap
    # another's arithmetic (Mosaic unrolls a loop wholly or not at all)
    per_trip = next(u for u in (8, 4, 2, 1) if hb % u == 0)

    def trip(i, ys):
        for k in range(per_trip):
            ys = head(i * per_trip + k, ys)
        return ys

    # the slot's y block is revisited by its blocks of heads in turn
    ys = jnp.where(j == 0, jnp.zeros((P, H), jnp.float32), y_ref[...])
    y_ref[...] = lax.fori_loop(0, hb // per_trip, trip, ys)


def state_update(pool: jax.Array, layer, rows: jax.Array, decay: jax.Array,
                 dtx: jax.Array, Bm: jax.Array, Cm: jax.Array, *,
                 interpret: bool = False):
    """One token a slot, in place: pool `[L, R, H, P, N]` float32 (donated
    by the caller's program: it is aliased to the result), `layer` its
    layer, rows `[S]` the slots' rows, decay `[S, H]` = exp(dt A), dtx
    `[S, H, P]` = dt x, Bm and Cm `[S, G, N]`, all float32 -> (y `[S, H,
    P]` float32 = S' C, without the `D x` term, and the pool with
    `pool[layer, rows[s]] = decay S + dtx B^T` for every slot)."""
    S, H, P = dtx.shape
    G, N = Bm.shape[1:]
    hb = _heads_per_block(pool)
    f32 = jnp.float32
    per_slot = lambda s, j, *_: (s, 0, 0)           # noqa: E731
    row_block = lambda s, j, layer, rows: (layer[0], rows[s], j, 0, 0)  # noqa: E731,E501
    state_spec = pl.BlockSpec((None, None, hb, P, N), row_block)
    y, pool = pl.pallas_call(
        lambda *refs: _kernel(*refs, per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, H // hb),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((None, P, H), per_slot),
                pl.BlockSpec((None, G, N), per_slot),
                pl.BlockSpec((None, G, N), per_slot),
                state_spec,
            ],
            out_specs=[pl.BlockSpec((None, P, H), per_slot), state_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, P, H), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      decay.astype(f32), jnp.swapaxes(dtx.astype(f32), 1, 2),
      Bm.astype(f32), Cm.astype(f32), pool)
    y = jnp.swapaxes(y, 1, 2)
    return y, pool


# channels a piece of the selective body: [16, 512] float32 is 8 registers
# an operand
_LANES = 512
_SLOT_TILE = 8      # slots a block of the row operands: a sublane tile


def use_selective_kernel(x: jax.Array, pool: jax.Array) -> bool:
    """Whether the selective recurrence's state update takes the kernel:
    on one TPU, over a float32 pool `[L, R, N, C]` whose rows are whole
    tiles (N whole sublane tiles, C whole pieces of `_LANES` channels: a
    narrower piece's row pick out of a tile of slots is a dynamic load
    Mosaic refuses, tests/test_tpu_aot_compile.py)."""
    if pool.ndim != 4 or pool.dtype != jnp.float32:
        return False
    N, C = pool.shape[2:]
    return _pa._on_one_tpu(x) and N % 8 == 0 and C % _LANES == 0


def _selective_kernel(layer_ref, rows_ref, dt_ref, dtx_ref, a_ref, b_ref,
                      c_ref, pool_ref, y_ref, out_ref):
    at = pl.ds(pl.program_id(0) % _SLOT_TILE, 1)
    C = pool_ref.shape[1]
    b, c = b_ref[...], c_ref[...]                               # [N, 1]
    step = min(_LANES, C)
    for first in range(0, C, step):
        lanes = pl.ds(first, step)
        new = jnp.exp(dt_ref[at, lanes] * a_ref[:, lanes]) \
            * pool_ref[:, lanes] + dtx_ref[at, lanes] * b        # [N, step]
        out_ref[:, lanes] = new
        y_ref[at, lanes] = jnp.sum(new * c, axis=0, keepdims=True)


def selective_update(pool: jax.Array, layer, rows: jax.Array, dt: jax.Array,
                     dtx: jax.Array, A: jax.Array, Bm: jax.Array,
                     Cm: jax.Array, *, interpret: bool = False):
    """One token a slot of the selective recurrence, in place: pool `[L, R,
    N, C]` float32 (donated by the caller's program: it is aliased to the
    result), `layer` its layer, rows `[S]` the slots' rows, dt `[S, C]`,
    dtx `[S, C]` = dt x, A `[N, C]` (negative), Bm and Cm `[S, N]`, all
    float32 -> (y `[S, C]` float32 = sum_n S' C, without the `D x` term,
    and the pool with `pool[layer, rows[s]] = exp(dt A) S + dtx B` for
    every slot)."""
    S, C = dt.shape
    N = A.shape[0]
    f32 = jnp.float32
    pad = -S % _SLOT_TILE
    tiled = lambda a: jnp.pad(a.astype(f32), [(0, pad), (0, 0)])  # noqa: E731
    by_tile = pl.BlockSpec((_SLOT_TILE, C),
                           lambda s, *_: (s // _SLOT_TILE, 0))
    column = pl.BlockSpec((None, N, 1), lambda s, *_: (s, 0, 0))
    row_spec = pl.BlockSpec(
        (None, None, N, C), lambda s, layer, rows: (layer[0], rows[s], 0, 0))
    y, pool = pl.pallas_call(
        _selective_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[by_tile, by_tile,
                      pl.BlockSpec((N, C), lambda s, *_: (0, 0)),
                      column, column, row_spec],
            out_specs=[by_tile, row_spec]),
        out_shape=[jax.ShapeDtypeStruct((S + pad, C), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_selective_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      tiled(dt), tiled(dtx), A.astype(f32), Bm.astype(f32)[..., None],
      Cm.astype(f32)[..., None], pool)
    return y[:S], pool
