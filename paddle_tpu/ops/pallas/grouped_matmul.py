"""Grouped (ragged) matrix multiplication: the rows of `x` are sorted by
group and group g's rows are multiplied by `w[g]` — the expert layer of a
sparse mixture (models/olmoe.py).

Two routes, one gate, as in `attention.py`. On a TPU, at shapes its tiles
divide, the Pallas `megablox` kernel that ships with jax; everywhere else
XLA's `jax.lax.ragged_dot`. Measured on the v5e at OLMoE's shapes (64
experts of 2048 x 1024, bf16; PERF.md section 6, PR 27): megablox at
tiles (128, 1024, 1024) reads a 16-row decode step's experts at 0.73-0.81
of the HBM roofline and a 64-256-token prefill's at 0.5-0.75, XLA's own
TPU kernel for `ragged_dot` at 0.61-0.64 and 0.33; and XLA's kernel
reaches the profile as `ragged-dot-none`, without the `op_name` of the
call that made it, so its time falls under no layer scope. Both skip a
group without rows at no cost, so a stack of every layer's experts can be
addressed in place. No interpreter route: off the chip the gate picks
XLA, and tests/test_tpu_aot_compile.py compiles the kernel for a
described v5e.
"""

from __future__ import annotations

import collections

import jax

from . import attention as _attention

# tiles (rows, contraction, columns) of the megablox kernel: rows are
# (token, expert) pairs, so 16 decode slots x 8 experts fill one tile. The
# contraction and column tiles are the most that `_tile` finds under these
TILES = (128, 1024, 1024)

# which route each trace took ("megablox" | "xla"), as attention's counts
GATE_COUNTS: collections.Counter = collections.Counter()


def _tile(size: int, cap: int) -> int:
    """The tile of a dimension of `size` whole lane tiles: `size` itself
    under the cap, else the largest multiple of 128 up to `cap` that
    divides it (2048 -> 1024; 2688 = 21 x 128 -> 896; 1920 -> 640)."""
    if size <= cap:
        return size
    return max(t for t in range(128, cap + 1, 128) if size % t == 0)


def _use_megablox(x, w) -> bool:
    m, k = x.shape
    n = w.shape[-1]
    return (_attention._platform(x) == "tpu"
            and _attention._mesh_partitionable(x)
            and m % TILES[0] == 0 and k % 128 == 0 and n % 128 == 0)


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """x [m, k] with its rows sorted by group, w [g, k, n], group_sizes [g]
    int32 summing to m -> [m, n] in x's dtype, accumulated in float32. A
    row's result depends on that row and its group's matrix alone."""
    if _use_megablox(x, w):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = TILES
        GATE_COUNTS["megablox"] += 1
        return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                   tiling=(tm, _tile(x.shape[1], tk),
                           _tile(w.shape[-1], tn)))
    GATE_COUNTS["xla"] += 1
    return jax.lax.ragged_dot(x, w, group_sizes)
