"""Grouped (ragged) matrix multiplication: the rows of `x` are sorted by
group and group g's rows are multiplied by `w[g]` — the expert layer of a
sparse mixture (models/moe.py).

Two routes, one gate, as in `attention.py`. On a TPU, at widths its tiles
divide, the Pallas `megablox` kernel that ships with jax (rows that are not
whole tiles of `ROW_TILE`, 48 decode slots x 10 experts, are filled up with
rows of no group, which the kernel visits no tile for); everywhere else
XLA's `jax.lax.ragged_dot`. XLA's own TPU kernel for `ragged_dot` read
OLMoE's experts at 0.61-0.64 of the HBM roofline where megablox read them
at 0.73-0.81 (PERF.md section 6, PR 27), and reaches the profile as
`ragged-dot-none`, without the `op_name` of the call that made it, so its
time falls under no layer scope. Both skip a group without rows at no
cost, so a stack of every layer's experts can be addressed in place. No
interpreter route: off the chip the gate picks XLA, and
tests/test_tpu_aot_compile.py compiles the kernel for a described v5e.

The kernel's tiles are chosen from the operands' shapes alone (`tiles`):
of the pairs (tk, tn) of whole lane tiles that divide K and N, whose
weight tile `[tk, tn]` is within `TILE_BYTES` and whose buffers fit the
kernel's VMEM, the one that ranks first by (the columns whole, the larger
tile, the longer columns). Measured on the v5e, the kernel alone at the
four sparse models' decode shapes and six more widths (PERF.md section 6,
PR 47; a tiling's time repeats to 0.1 us; in a serve program the same
calls run 2-4% faster, in the same order): no tiling reads a call's
weights faster than 750 GB/s. A tile of WHOLE COLUMNS is one contiguous
piece of the matrix, read in the one turn the kernel's outer loop over
column tiles then has, and reads at 735-742 GB/s at every width (2688 x
1920 through (896, 1920): 1393 us a call of 100 experts hit; through the
(896, 640) that two caps of 1024 lanes used to pick: 1547). A tile of cut
columns is pieces strided by the row's pitch and reads at 623-750 GB/s by
a law of N and tn the measurements do not give (N = 1920 cut at 384 or
640 lanes: 623 and 674 whatever K is; N = 1024 or 2048 at 256: 677-698;
N = 2688 at 384: 742), though a whole contraction over cut columns, which
fetches the rows' tile once and not at every step, is the best form by
0.1-3% wherever it is not slow. So the rule takes the form that is never
slow. Among whole columns the tile's size hardly counts ((384, 1920):
1392 us); where neither dimension was whole the longer columns won at
equal bytes.

The backward pass (`megablox.gmm`'s `custom_vjp`: a transposed `gmm` and
`tgmm`) takes the same tuple; `tgmm` holds a float32 accumulator and two
output tiles of the weight tile's shape, which is what `TILE_BYTES` is
sized by. No benchmark cell trains an expert layer;
tests/test_tpu_aot_compile.py compiles a gradient at the widest tiles.
"""

from __future__ import annotations

import collections
from typing import Dict, Tuple

import jax

from . import attention as _attention

# rows of a tile of the megablox kernel: rows are (token, expert) pairs, so
# 16 decode slots x 8 experts fill one; lanes of a vector register
ROW_TILE = 128
LANES = 128
# what a kernel may use of VMEM: `gmm` takes no `vmem_limit_bytes`
VMEM_BYTES = 16 * 2 ** 20
# the most a weight tile `[tk, tn]` may hold, 3.75 MiB. Step 0 of PR 47 (the
# kernel alone on the v5e, PERF.md section 6): tiles of 3.1-3.7 MB read their
# bytes as fast as or faster than the 1.1-2.1 MB the caps of 1024 lanes
# gave (740-750 GB/s against 666-748), a tile of 4.19 MB no faster than one
# of 2.10 (OLMoE's 2048 x 1024: 317.0 us a call against 315.3-320.4); and at
# 4 MiB the backward pass's `tgmm` (float32 accumulator + two output tiles
# of this shape) asks for 16.38 MiB of the 16, where at 3.75 it compiles
TILE_BYTES = 15 * 2 ** 18

# which route each trace took ("megablox" | "xla"), as attention's counts
GATE_COUNTS: collections.Counter = collections.Counter()
# the tiles the kernel's traces took: {(K, N): (tm, tk, tn)}
TILES: Dict[Tuple[int, int], Tuple[int, int, int]] = {}


def planned_vmem(tiling: Tuple[int, int, int], itemsize: int) -> int:
    """Bytes of VMEM the forward kernel holds at `tiling`: the weight tile,
    the rows' tile and the output tile twice each (a copy in flight beside
    the one in use) and the float32 accumulator; what the TPU's compiler
    counts, to the byte (22.56 MiB at (128, 128, 15360) in bf16)."""
    tm, tk, tn = tiling
    return 2 * (tk * tn + tm * tk + tm * tn) * itemsize + 4 * tm * tn


def _divisors(size: int):
    return [t for t in range(LANES, size + 1, LANES) if size % t == 0]


def tiles(k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) for operands `[m, k]` x `[g, k, n]` of `itemsize` bytes
    an element, `k` and `n` whole lane tiles. Of the divisors' pairs whose
    weight tile is within `TILE_BYTES` and whose buffers fit `VMEM_BYTES`
    (one of 128 x 128 always does), the first by: the columns whole, then
    the larger tile, then the longer columns. 2688 x 1920 -> (896, 1920),
    1920 x 2688 -> (640, 2688), 2048 x 768 -> the matrix whole,
    2048 x 1024 -> (1024, 1024), 1024 x 2048 -> (512, 2048)."""
    fits = [(tk, tn) for tk in _divisors(k) for tn in _divisors(n)
            if tk * tn * itemsize <= TILE_BYTES
            and planned_vmem((ROW_TILE, tk, tn), itemsize) <= VMEM_BYTES]
    tk, tn = max(fits, key=lambda t: (t[1] == n, t[0] * t[1], t[1]))
    return ROW_TILE, tk, tn


def _use_megablox(x, w) -> bool:
    k, n = w.shape[-2:]
    return (_attention._platform(x) == "tpu"
            and _attention._mesh_partitionable(x)
            and k % LANES == 0 and n % LANES == 0)


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """x [m, k] with its rows sorted by group, w [g, k, n], group_sizes [g]
    int32 summing to m -> [m, n] in x's dtype, accumulated in float32. A
    row's result depends on that row and its group's matrix alone."""
    if _use_megablox(x, w):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        k, n = w.shape[-2:]
        tiling = TILES[k, n] = tiles(k, n, w.dtype.itemsize)
        GATE_COUNTS["megablox"] += 1
        # whole tiles of rows: the rows past the groups' sum belong to no
        # group and come back unwritten
        m = x.shape[0]
        if m % ROW_TILE:
            x = jax.numpy.pad(x, [(0, -m % ROW_TILE), (0, 0)])
        out = gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                  tiling=tiling)
        return out[:m] if m % ROW_TILE else out
    GATE_COUNTS["xla"] += 1
    return jax.lax.ragged_dot(x, w, group_sizes)
