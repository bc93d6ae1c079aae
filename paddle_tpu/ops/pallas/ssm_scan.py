"""A prompt's selective scan (Mamba-1: `ops/ssm.selective_recurrent`'s
recurrence) with the state in VMEM.

Through XLA a prompt's recurrence is a handful of small fused ops a token,
each a pass over the `[N, channels]` state: 19.5 us a token over 26 layers
at 16 x 5120 where the arithmetic is a quarter of that (chip run of PR 49,
step 0). This kernel walks (sequence, block of channels, block of tokens)
with the tokens innermost: a block of `_LANES` channels keeps its state
`[N, _LANES]` float32 (8 registers) across the whole prompt, in a VMEM
scratch between blocks of tokens and in registers inside one, and a token
costs the state's arithmetic and nothing else:

    S = exp(dt_t A) S + (dt_t x_t) B_t;   y_t = sum_n S C_t

`dt` and `dt x` arrive as rows `[tokens, channels]` (a token a sublane: a
tile of eight tokens is loaded whole and a token's row is a static slice of
it, broadcast down the state's sublanes); B and C as columns `[tokens, N,
1]` (a token's column is indexed on the leading, untiled axis and broadcast
along the lanes; its padding to a lane tile is 8 KB a token, a fortieth of
what a state row is); `A` `[N, channels]` once a block of channels. The
decay is computed here, as in `ssm_update.selective_update`. A position
whose `dt` is 0 leaves the state as it was, exactly (`exp(0) S + 0`): how a
prompt padded to its bucket keeps the padding out; its `y` is finite.

Two routes, one gate (`use_kernel`), the pick final; the route is counted
in `ssm_update.GATE_COUNTS` (`"scan_kernel"` | `"scan_xla"`, a count a
recurrent layer of a prefill program), which `DecodeEngine.status()`
reports beside the decode step's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

_LANES = 512        # channels a block: [16, 512] float32 is 8 registers
_TOKENS = 128       # tokens a block at most: B's and C's columns are 1 MB
_GROUP = 8          # tokens a tile of the row operands


def _token_block(T: int) -> int:
    """Tokens a block: the largest power of two up to `_TOKENS` that
    divides T (0 where not even a tile of eight does)."""
    tb = _TOKENS
    while tb >= _GROUP and T % tb:
        tb //= 2
    return tb if tb >= _GROUP else 0


def use_kernel(x: jax.Array, n_state: int) -> bool:
    """Whether a prompt's selective scan takes the kernel: on one TPU, x
    `[B, T, C]` with C whole blocks of `_LANES` channels, T whole tiles of
    eight tokens and the state's lanes whole sublane tiles."""
    if x.ndim != 3:
        return False
    _, T, C = x.shape
    return (_pa._on_one_tpu(x) and C % _LANES == 0 and n_state % 8 == 0
            and _token_block(T) > 0)


def _kernel(dt_ref, dtx_ref, a_ref, b_ref, c_ref, y_ref, last_ref, s_ref):
    tb = dt_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    A = a_ref[...]                                          # [N, lanes]

    def group(g, S):
        base = pl.multiple_of(g * _GROUP, _GROUP)
        dt8 = dt_ref[pl.ds(base, _GROUP), :]                # [8, lanes]
        dtx8 = dtx_ref[pl.ds(base, _GROUP), :]
        rows = []
        for j in range(_GROUP):
            S = jnp.exp(dt8[j:j + 1, :] * A) * S \
                + dtx8[j:j + 1, :] * b_ref[base + j]        # [N, lanes]
            rows.append(jnp.sum(S * c_ref[base + j], axis=0, keepdims=True))
        y_ref[pl.ds(base, _GROUP), :] = jnp.concatenate(rows, axis=0)
        return S

    S = lax.fori_loop(0, tb // _GROUP, group, s_ref[...])
    s_ref[...] = S
    last_ref[...] = S


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, *, interpret: bool = False):
    """Whole sequences from a zero state: x `[B, T, C]`, dt `[B, T, C]`
    float32 (0 where a position must not count), A `[N, C]` (negative), Bm
    and Cm `[B, T, N]` -> (y `[B, T, C]` float32 = sum_n S_t C_t, WITHOUT
    the `D x` term, and the state after the last position `[B, N, C]`
    float32)."""
    B, T, C = x.shape
    N = A.shape[0]
    f32 = jnp.float32
    tb = _token_block(T)
    lanes = min(_LANES, C)
    dt = dt.astype(f32)
    rows = pl.BlockSpec((None, tb, lanes), lambda b, c, t: (b, t, c))
    cols = pl.BlockSpec((None, tb, N, 1), lambda b, c, t: (b, t, 0, 0))
    y, last = pl.pallas_call(
        _kernel,
        grid=(B, C // lanes, T // tb),
        in_specs=[rows, rows,
                  pl.BlockSpec((N, lanes), lambda b, c, t: (0, c)),
                  cols, cols],
        out_specs=[rows,
                   pl.BlockSpec((None, N, lanes), lambda b, c, t: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), f32),
                   jax.ShapeDtypeStruct((B, N, C), f32)],
        scratch_shapes=[pltpu.VMEM((N, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_selective_scan",
    )(dt, dt * x.astype(f32), A.astype(f32), Bm.astype(f32)[..., None],
      Cm.astype(f32)[..., None])
    return y, last
