"""The decode step's state-space update, in place in the state row pool,
for the two recurrences of `ops/ssm.py`: `state_update` / `use_kernel` the
one whose decay is ONE SCALAR A HEAD (Mamba-2, lightning attention:
`ssd_step`), `selective_update` / `use_selective_kernel` the one whose
decay differs a channel AND a state lane (Mamba-1: `selective_step`; its
section is at the end of this docstring). Both count their route in
`GATE_COUNTS`. The convolution's tails of a Mamba-1 layer are a row pool of
their own, advanced in place by `advance_tails` / `use_tail_kernel` (counted
`"tail_kernel"` | `"tail_xla"`).

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
`exp(dt A)` in would double the bytes. The rows `dt`, `dt x` and `y` are
`[slots, channels]` arrays taken a sublane tile of eight slots a block or a
grid step's slots, whichever is more (a slot alone would be padded to a
tile in HBM and cost half a state row), and a slot's row picked by its
index in the block; B and C arrive as `[slots, N, 1]`, whose padding to a
lane tile is 8 KB a slot, a fortieth of the row. The body goes through a
row in pieces of `_LANES` channels so that a piece's values stay in
registers.

THE ROW WALK (`_row_walk`; `selective_update` and `advance_tails` run on
it). A Mamba-1 layer keeps two row pools, the states above and the
convolution's tails (`[120, 128]` bf16, 30 KB a row), and a decode step
advances the slots' rows of both. The pool enters the kernel unblocked, in
HBM, aliased to its output, and a grid step takes SEVERAL rows by the
kernel's own copies: `rows_per_step` rows `pool[layer, rows[s]] ->
scratch`, the body over them in VMEM, the same rows back to where they came
from. Two scratch slots: step g+1's reads are in flight while step g is
computed, step g's writes drain while step g+1 begins and are waited for
before step g+2's reads take their slot, so reads and writes alternate.
How many rows, from the shapes alone (no knob): the power of two that
brings a step's reads nearest 2 MB, 8 state rows or 64 tails, and no more
than cover the slots. Why (chip runs of PR 51, 26 layers x 128 slots, ms a
decode step): the states through the blocked pipeline, a row a grid step,
took 3.80, and 3.71 WITH THE BODY CUT TO A COPY, so neither the arithmetic
nor, as the walk then showed, the grid step held the time: a row a step of
the walk 3.84, four 3.66, eight 3.60 (606 GB/s), the walk with no body
3.65: an in-place pass, whose every byte is read and written, moves at
600-640 GB/s on this chip however it is cut (a read-only paged walk
reaches 720-765). The tails gain what the issue hoped the states would:
gathered, stepped by `ops/ssm.conv_step` and scattered back through XLA
they crossed HBM six times (1.17 ms a step in the program), walked 64 rows
a step twice (0.32 ms). Three scratch slots, which let reads and writes
overlap, were slower than two at the same rows a step (3.66 against 3.60,
0.35 against 0.32).

Row 0 in the walk. The slots' rows are filled up to whole steps with row
0, the null row that idle slots already carry several times over. Its
copies land on one another in no order, and a body that met a half-written
row 0 writes garbage back to it: nothing reads row 0 for a live sequence. A
LIVE row appears once among a step's rows (the allocator's guarantee), so
no copy of one races another.

The tails (`advance_tails`): a position's 5120 bf16 channels are 40 rows
of 128 lanes, 2.5 bf16 tiles, so in bf16 a shift by one position is no
whole-tile move. The body widens the fetched `[120, 128]` block to float32
(15 whole tiles of 8 rows; bf16 -> float32 -> bf16 is exact), takes rows
40: beside the token's `[40, 128]`, does the convolution's products there
and narrows the whole block back: the row keeps `state_pools`' layout, the
last K-1 inputs end to end, and `ssm_prompt` and the gathered form agree
with it bit for bit.
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

from . import paged_attention as _pa

# which route each traced state update took ("kernel" | "xla"; a Mamba-1
# layer's tails "tail_kernel" | "tail_xla"), a count a recurrent layer of a
# decode program; DecodeEngine.status() reports them
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
    groups, or a whole part of ONE group (128 heads on one B and C go 32 a
    block: the body picks a head's group by `head // per_group` and B and
    C arrive as the slot's whole `[G, N]`, so a block inside a group reads
    the one row every head of it shares)."""
    if pool.ndim != 5 or pool.dtype != jnp.float32:
        return False
    H, P, N = pool.shape[2:]
    hb = _heads_per_block(pool)
    per_group = H // groups
    return (_pa._on_one_tpu(x) and H % groups == 0 and P % 8 == 0
            and N % 128 == 0
            and (hb % per_group == 0 or per_group % hb == 0))


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

# the row walk (the module docstring's THE ROW WALK): bytes a grid step reads,
# about, and its scratch slots. Chip runs of PR 51, 128 slots x 26 layers,
# ms a decode step: the states 3.84 / 3.67 / 3.66 / 3.66 at 1 / 2 / 4 / 8
# rows a step over three slots, 3.70 / 3.60 at 4 / 8 rows over two; the
# tails 0.41 / 0.36 / 0.35 / 0.32 at 8 / 16 / 32 / 64 rows over three slots,
# 0.32 / 0.32 at 32 / 64 over two
_STEP_BYTES = 2 << 20
_RING = 2
_TAIL_LANES = 128   # lanes a row of the tails' pool: `state_pools`' choice


def rows_per_step(row_bytes: int, slots: int) -> int:
    """Rows a grid step of the row walk moves: the power of two that brings
    a step's reads nearest `_STEP_BYTES`, and no more than cover `slots`."""
    want = 1 << max(0, round(math.log2(_STEP_BYTES / row_bytes)))
    return min(want, 1 << max(0, slots - 1).bit_length())


def _walk_rows(rows: jax.Array, R: int) -> jax.Array:
    """`rows` filled up to whole steps of R with row 0, the null row."""
    return jnp.pad(rows.astype(jnp.int32), (0, -rows.shape[0] % R))


def _row_walk(layer_ref, rows_ref, pool, buf, sems, body):
    """One grid step of the walk over `pool[layer, rows]`, R = `buf.shape[1]`
    rows a step through the scratch slots `buf` `[_RING, R, *row]` (`sems`
    `[_RING, 2]`: a slot's reads, its writes): step g's rows arrive while
    step g-1 is computed, `body(slot)` advances them in `buf[slot]`, and
    they go back to where they came from. A slot is read into again only
    when the writes out of it have landed. `rows_ref` holds whole steps
    (`_walk_rows`)."""
    g, G = pl.program_id(0), pl.num_programs(0)
    n, R = buf.shape[:2]
    layer = layer_ref[0]

    def copies(step, act, back):
        slot = step % n

        def one(r, carry):
            row = pool.at[layer, rows_ref[step * R + r]]
            src, dst = (buf.at[slot, r], row) if back \
                else (row, buf.at[slot, r])
            getattr(pltpu.make_async_copy(
                src, dst, sems.at[slot, int(back)]), act)()
            return carry

        lax.fori_loop(0, R, one, 0)

    @pl.when(g == 0)
    def _():
        copies(0, "start", False)

    @pl.when(g + 1 < G)
    def _():
        @pl.when(g + 1 >= n)
        def _():
            copies(g + 1 - n, "wait", True)

        copies(g + 1, "start", False)

    copies(g, "wait", False)
    body(g % n)
    copies(g, "start", True)

    @pl.when(g == G - 1)
    def _():
        for last in range(n):           # the writes no later read waited for
            @pl.when(G - 1 - last >= 0)
            def _(last=last):
                copies(G - 1 - last, "wait", True)


def _walk_call(kernel, pool, R, in_specs, out_spec, out_shape, operands, *,
               name, interpret):
    """`kernel` over the grid of the row walk: `operands` = (layer, rows,
    *blocked operands), rows in whole steps of R; the pool goes in last,
    unblocked, aliased to the last output; the scratch is the walk's slots
    and their semaphores."""
    steps = operands[1].shape[0] // R
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=list(in_specs) + [hbm],
            out_specs=[out_spec, hbm],
            scratch_shapes=[
                pltpu.VMEM((_RING, R) + pool.shape[2:], pool.dtype),
                pltpu.SemaphoreType.DMA((_RING, 2))]),
        out_shape=[out_shape, jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*operands, pool)


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
                      c_ref, pool_in, y_ref, pool, buf, sems):
    del pool_in                 # the same buffer as `pool`
    R, C = buf.shape[1], buf.shape[3]
    # where this step's rows lie in the block of the row operands
    first_slot = (pl.program_id(0) * R) % dt_ref.shape[0]
    step = min(_LANES, C)

    def body(slot):
        def row(r, carry):
            at = pl.ds(first_slot + r, 1)
            b, c = b_ref[r], c_ref[r]                           # [N, 1]
            for first in range(0, C, step):
                lanes = pl.ds(first, step)
                new = jnp.exp(dt_ref[at, lanes] * a_ref[:, lanes]) \
                    * buf[slot, r, :, lanes] + dtx_ref[at, lanes] * b
                buf[slot, r, :, lanes] = new                    # [N, step]
                y_ref[at, lanes] = jnp.sum(new * c, axis=0, keepdims=True)
            return carry

        lax.fori_loop(0, R, row, 0)

    _row_walk(layer_ref, rows_ref, pool, buf, sems, body)


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
    R = rows_per_step(math.prod(pool.shape[2:]) * pool.dtype.itemsize,
                      rows.shape[0])
    return _selective_update(pool, layer, rows, dt, dtx, A, Bm, Cm, R=R,
                             interpret=interpret)


# jitted, so that a decode program's layers share one trace and one lowering
# of the kernel (26 calls of one function; XLA inlines them)
@functools.partial(jax.jit, static_argnames=("R", "interpret"))
def _selective_update(pool, layer, rows, dt, dtx, A, Bm, Cm, *, R, interpret):
    S, C = dt.shape
    N = A.shape[0]
    f32 = jnp.float32
    rows = _walk_rows(rows, R)
    tile = max(R, _SLOT_TILE)
    to = lambda a, n: jnp.pad(                                  # noqa: E731
        a.astype(f32), [(0, n - S)] + [(0, 0)] * (a.ndim - 1))
    slots = S + -S % tile
    by_tile = pl.BlockSpec((tile, C), lambda g, *_: (g * R // tile, 0))
    column = pl.BlockSpec((R, N, 1), lambda g, *_: (g, 0, 0))
    y, pool = _walk_call(
        _selective_kernel, pool, R,
        [by_tile, by_tile, pl.BlockSpec((N, C), lambda g, *_: (0, 0)),
         column, column],
        by_tile, jax.ShapeDtypeStruct((slots, C), f32),
        (jnp.reshape(layer, (1,)).astype(jnp.int32), rows, to(dt, slots),
         to(dtx, slots), A.astype(f32), to(Bm[..., None], rows.shape[0]),
         to(Cm[..., None], rows.shape[0])),
        name="ssm_selective_update", interpret=interpret)
    return y[:S], pool


def use_tail_kernel(x: jax.Array, pool: jax.Array, taps: int) -> bool:
    """Whether the convolution's tails are advanced where they lie: on one
    TPU, over a pool `[L, R, rows, 128]` of whole lane tiles in which each
    of a row's `taps - 1` positions is whole float32 sublane tiles
    (`advance_tails` widens a row to float32 in VMEM)."""
    if pool.ndim != 4 or pool.shape[3] != _TAIL_LANES \
            or pool.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    return _pa._on_one_tpu(x) and pool.shape[2] % (8 * (taps - 1)) == 0


def _tail_kernel(layer_ref, rows_ref, x_ref, w_ref, b_ref, pool_in, out_ref,
                 pool, buf, sems):
    del pool_in                 # the same buffer as `pool`
    R, P = x_ref.shape[:2]      # P tiles of lanes a position
    taps = w_ref.shape[0]
    f32 = jnp.float32

    def body(slot):
        def row(r, carry):
            # widened, a position is whole sublane tiles whatever the pool's
            # dtype packs into one; and back is exact
            tail = buf[slot, r].astype(f32)             # [(taps-1) P, 128]
            x = x_ref[r]                                        # [P, 128]
            acc = w_ref[0] * tail[:P]
            for k in range(1, taps - 1):
                acc = acc + w_ref[k] * tail[k * P:(k + 1) * P]
            out_ref[r] = b_ref[...] + (acc + w_ref[taps - 1] * x)
            buf[slot, r] = jnp.concatenate([tail[P:], x], axis=0) \
                .astype(buf.dtype)
            return carry

        lax.fori_loop(0, R, row, 0)

    _row_walk(layer_ref, rows_ref, pool, buf, sems, body)


def advance_tails(pool: jax.Array, layer, rows: jax.Array, x: jax.Array,
                  w: jax.Array, b: jax.Array, *, interpret: bool = False):
    """`ops/ssm.conv_step` where the tails lie: pool `[L, R, (K-1) C / 128,
    128]` (a row the last K-1 inputs end to end; donated by the caller's
    program: it is aliased to the result), `layer` its layer, rows `[S]` the
    slots' rows, x `[S, C]` the token's inputs, w `[K, C]`, b `[C]` -> (out
    `[S, C]` in x's dtype, accumulated in float32: the convolution at the
    token BEFORE any activation, and the pool with every named row's tail
    moved on by x, rounded to the pool's dtype)."""
    R = rows_per_step(math.prod(pool.shape[2:]) * pool.dtype.itemsize,
                      rows.shape[0])
    return _advance_tails(pool, layer, rows, x, w, b, R=R,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("R", "interpret"))   # as above
def _advance_tails(pool, layer, rows, x, w, b, *, R, interpret):
    S, C = x.shape
    K = w.shape[0]
    P = C // _TAIL_LANES
    f32 = jnp.float32
    rows = _walk_rows(rows, R)
    # what the tail keeps of x is x in the pool's dtype, as `conv_step`'s
    xs = jnp.pad(x.astype(pool.dtype).astype(f32),
                 [(0, rows.shape[0] - S), (0, 0)])
    by_step = pl.BlockSpec((R, P, _TAIL_LANES), lambda g, *_: (g, 0, 0))
    out, pool = _walk_call(
        _tail_kernel, pool, R,
        [by_step,
         pl.BlockSpec((K, P, _TAIL_LANES), lambda g, *_: (0, 0, 0)),
         pl.BlockSpec((P, _TAIL_LANES), lambda g, *_: (0, 0))],
        by_step, jax.ShapeDtypeStruct((rows.shape[0], P, _TAIL_LANES), f32),
        (jnp.reshape(layer, (1,)).astype(jnp.int32), rows,
         xs.reshape(-1, P, _TAIL_LANES),
         w.astype(f32).reshape(K, P, _TAIL_LANES),
         b.astype(f32).reshape(P, _TAIL_LANES)),
        name="ssm_advance_tails", interpret=interpret)
    return out[:S].reshape(S, C).astype(x.dtype), pool
