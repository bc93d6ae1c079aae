"""The recurrences of the recurrent mixers (state-space layers: Mamba-2's
"SSD" and Mamba-1's selective scan; linear attention) and the causal
depthwise convolution before a state-space layer, in the forms a served
model needs. TWO recurrences live here, told apart by what a decay is:

ONE SCALAR A HEAD (`ssd_step`, `ssd_recurrent`, `ssd_chunked`,
`linear_attention_args`: Mamba-2, lightning attention). A head h keeps a
state `S` [P, N] (P the head's width, N the state size). With `a_t = dt_t *
A_h` (A negative, dt positive) a token does

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T          x_t [P], B_t [N]
    y_t = S_t C_t + D_h x_t                          C_t [N]

B and C belong to a GROUP of heads (`G` groups, H/G heads each).

The DECAY `exp(a_t)` has two forms, and the `ssd_*` functions are told it
through `dt` and `A` alone:

- input-dependent (Mamba-2): `dt_t` is a softplus of the token's own
  projection, `A_h` a learned scalar a head;
- fixed a head (linear attention with decay, "lightning attention"): `S_t
  = exp(-s_h) S_{t-1} + v_t k_t^T`, `o_t = S_t q_t`, which is the recurrence
  above with x = v, B = k, C = q, one group a head (G = H), `A_h = -s_h`,
  `D = 0` and `dt_t = 1` for a token that counts (`linear_attention_args`).

- `ssd_chunked`: a whole prompt. The sequence is cut into chunks of
  `chunk` tokens; inside a chunk the recurrence is the quadratic form
  `y_i = sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j` (cs the running
  sum of `a` in the chunk: two matmuls and a mask), between chunks only
  the state moves: `S_c+1 = exp(cs_last) S_c + sum_j exp(cs_last - cs_j)
  dt_j x_j B_j^T`, and a token reads the state its chunk began with,
  `exp(cs_i) C_i . S_c`. Decay sums and their exponentials are float32;
  the products run in the inputs' dtype and accumulate in float32. A
  position whose `dt` is 0 (`dt_t = 0`: decay 1, no input) leaves the state
  as it was: how a prompt padded to its bucket keeps the padding out.
- `ssd_step`: one token a row, the recurrence as written.
- `ssd_recurrent`: the recurrence token by token over a sequence (a
  `lax.scan` of `ssd_step`): what the chunked form is tested against.

A DECAY A CHANNEL AND A STATE LANE (`selective_step`,
`selective_recurrent`: Mamba-1's selective scan). No
heads: every one of the `C` channels keeps `N` state lanes of its own, `A`
is `[N, C]` (the published `A_log` is `[C, N]`; kept here with the channels
in the lanes, which is how a state row `[N, C]` is whole tiles at N = 16),
`dt_t` a value a channel, B_t and C_t `[N]` shared by all channels:

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n] + D[c] x_t[c]

The decay differs for each of a channel's lanes, so a chunk's decays do NOT
factor into one `[T, T]` mask a head and `ssd_chunked`'s quadratic form does
not exist here; the other factoring, `S_t = P_t (S_0 + sum_j b_j / P_j)`
with `P_t = exp(A cumsum(dt)_t)`, overflows float32 as soon as `dt |A|`
summed over a chunk passes 88, which 6 tokens at `dt` 1 and `A` -16 do.
A prompt therefore runs the recurrence AS WRITTEN: `selective_recurrent`, a
`lax.scan` of `selective_step` that carries `[B, N, C]` and holds no
temporary that grows with the prompt beyond the `[B, T, C]` output,
`SELECTIVE_UNROLL` tokens unrolled in its body so that the compiler sees
one straight line to fuse. `dt_t = 0` keeps a position out, as above.
On a TPU a prompt's scan keeps its state in VMEM
(`ops/pallas/ssm_scan.selective_scan`) and a decode step's rows are
advanced where they lie (`ops/pallas/ssm_update.selective_update`); these
functions are what both are tested against and what runs everywhere else.

`causal_conv` / `conv_step` are the width-K depthwise convolution over the
sequence: `out_t = b + sum_k w[k] x_{t-(K-1)+k}`, and its one-token form
over a tail of the last K-1 inputs; both recurrences' layers use them (on
a TPU a Mamba-1 layer's tails are moved on where they lie in their pool,
`ops/pallas/ssm_update.advance_tails`, which is tested against `conv_step`).

Every function is row-independent: a sequence's results depend on that
sequence alone. All ops carry the layer scope `ssm` of their caller.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def linear_attention_args(slopes: jax.Array, live: jax.Array):
    """(dt, A, D) that make `ssd_chunked` / `ssd_step` the linear-attention
    recurrence with a fixed decay a head: slopes `[H]` (a head forgets by
    `exp(-slopes[h])` a token), `live` `[...]` bool: which positions count (padding leaves the state as it was: dt 0). Call with
    x = v, Bm = k, Cm = q (scaled), one group a head."""
    f32 = jnp.float32
    slopes = slopes.astype(f32)
    dt = jnp.broadcast_to(live.astype(f32)[..., None],
                          live.shape + slopes.shape)
    return dt, -slopes, jnp.zeros_like(slopes)


def _led(x: jax.Array, K: int, before: Optional[jax.Array]) -> jax.Array:
    """x [B, T, C] with the K-1 inputs before it in front: zeros, or
    `before` [B, K-1, C] where x continues a sequence."""
    if before is None:
        return jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    return jnp.concatenate([before.astype(x.dtype), x], axis=1)


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                before: Optional[jax.Array] = None) -> jax.Array:
    """x [B, T, C], w [K, C], b [C] -> [B, T, C]: position t sees inputs
    t-K+1..t (zeros before the sequence, or `before` [B, K-1, C]: what
    `conv_tail` kept of the part of the sequence that came before x)."""
    K = w.shape[0]
    T = x.shape[1]
    xp = _led(x, K, before)
    out = b.astype(jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + T].astype(jnp.float32) \
            * w[k].astype(jnp.float32)
    return out.astype(x.dtype)


def conv_tail(x: jax.Array, length: jax.Array, K: int,
              before: Optional[jax.Array] = None) -> jax.Array:
    """The last K-1 inputs before position `length` of x [B, T, C] (zeros
    before the sequence, or `before` as in `causal_conv`) -> [B, K-1, C]:
    what `conv_step`, or the next part's `before`, continues from."""
    xp = _led(x, K, before)
    return jax.lax.dynamic_slice_in_dim(xp, length, K - 1, axis=1)


def conv_step(tail: jax.Array, x: jax.Array, w: jax.Array, b: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """One token: tail [S, K-1, C] (the inputs before it), x [S, C] ->
    (out [S, C], the new tail)."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    out = b.astype(jnp.float32) + jnp.sum(
        window.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1)
    return out.astype(x.dtype), window[:, 1:]


def ssd_step(state: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, Cm: jax.Array, D: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One token a row. state [S, H, P, N] float32, x [S, H, P], dt [S, H]
    float32, A and D [H], Bm and Cm [S, G, N] -> (y [S, H, P] float32, the
    new state)."""
    S, H, P, N = state.shape
    G = Bm.shape[1]
    f32 = jnp.float32
    xf = x.astype(f32)
    Bh = jnp.repeat(Bm.astype(f32), H // G, axis=1)         # [S, H, N]
    Ch = jnp.repeat(Cm.astype(f32), H // G, axis=1)
    decay = jnp.exp(dt * A.astype(f32))                     # [S, H]
    state = decay[..., None, None] * state \
        + (dt[..., None] * xf)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(state * Ch[:, :, None, :], axis=-1) \
        + D.astype(f32)[None, :, None] * xf
    return y, state


def ssd_recurrent(x, dt, A, Bm, Cm, D, init: Optional[jax.Array] = None):
    """The recurrence token by token: x [B, T, H, P], dt [B, T, H], Bm and
    Cm [B, T, G, N] -> (y [B, T, H, P] float32, the last state [B, H, P, N]
    float32)."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    state = jnp.zeros((B, H, P, N), jnp.float32) if init is None else init

    def one(state, t):
        y, state = ssd_step(state, t[0], t[1], A, t[2], t[3], D)
        return state, y

    move = lambda a: jnp.moveaxis(a, 1, 0)      # noqa: E731
    state, ys = jax.lax.scan(one, state,
                             (move(x), move(dt), move(Bm), move(Cm)),
                             unroll=SELECTIVE_UNROLL)
    return jnp.moveaxis(ys, 0, 1), state


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, D: jax.Array, chunk: int,
                init: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """A whole sequence in chunks of `chunk` tokens: x [B, T, H, P], dt
    [B, T, H] float32 (0 where a position must not count), A and D [H], Bm
    and Cm [B, T, G, N] -> (y [B, T, H, P] float32, the state after the
    last position [B, H, P, N] float32). `T` is padded to whole chunks with
    positions that do not count."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    f32 = jnp.float32
    pad = -T % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    nc, L = (T + pad) // chunk, chunk
    xc = x.reshape(B, nc, L, G, R, P)
    Bc = Bm.reshape(B, nc, L, G, N)
    Cc = Cm.reshape(B, nc, L, G, N)
    # per-head scalars with the chunk's positions minor-most: [B,nc,G,R,L]
    dtc = dt.astype(f32).reshape(B, nc, L, G, R).transpose(0, 1, 3, 4, 2)
    cs = jnp.cumsum(dtc * A.astype(f32).reshape(G, R, 1), axis=-1)
    by_pos = lambda a: a.transpose(0, 1, 4, 2, 3)   # noqa: E731 [B,nc,L,G,R]
    # inside a chunk: (C_i . B_j) exp(cs_i - cs_j) dt_j for j <= i
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((L, L), bool))
    # the exponent is masked BEFORE the exponential: cs_i - cs_j > 0 above
    # the diagonal, and its exponential may overflow
    gap = jnp.where(seen, cs[..., :, None] - cs[..., None, :], -jnp.inf)
    m = cb[:, :, :, None] * jnp.exp(gap) * dtc[..., None, :]  # [B,nc,G,R,i,j]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m.astype(x.dtype), xc,
                   preferred_element_type=f32)
    # what a chunk adds to the state, and how far the state it met decays
    last = cs[..., -1]                                      # [B, nc, G, R]
    to_end = by_pos(jnp.exp(last[..., None] - cs) * dtc)    # [B,nc,L,G,R]
    added = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                       (xc.astype(f32) * to_end[..., None]).astype(x.dtype),
                       Bc, preferred_element_type=f32)
    state = jnp.zeros((B, G, R, P, N), f32) if init is None \
        else init.reshape(B, G, R, P, N)

    def carry(state, c):
        return jnp.exp(c[0])[..., None, None] * state + c[1], state

    state, met = jax.lax.scan(
        carry, state, (jnp.moveaxis(last, 1, 0), jnp.moveaxis(added, 1, 0)))
    met = jnp.moveaxis(met, 0, 1)                           # [B,nc,G,R,P,N]
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", Cc, met.astype(x.dtype),
                       preferred_element_type=f32) \
        * by_pos(jnp.exp(cs))[..., None]
    y = y + D.astype(f32).reshape(G, R)[..., None] * xc.astype(f32)
    return (y.reshape(B, nc * L, H, P)[:, :T],
            state.reshape(B, H, P, N))


SELECTIVE_UNROLL = 8    # tokens a body of `selective_recurrent`'s scan


def selective_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                   A: jax.Array, Bm: jax.Array, Cm: jax.Array, D: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """One token a row of the selective recurrence. state [S, N, C]
    float32, x [S, C], dt [S, C] float32, A [N, C] (negative), Bm and Cm
    [S, N], D [C] -> (y [S, C] float32, the new state)."""
    f32 = jnp.float32
    xf = x.astype(f32)
    decay = jnp.exp(dt[:, None, :] * A.astype(f32)[None])       # [S, N, C]
    state = decay * state \
        + (dt * xf)[:, None, :] * Bm.astype(f32)[:, :, None]
    y = jnp.sum(state * Cm.astype(f32)[:, :, None], axis=1) \
        + D.astype(f32)[None] * xf
    return y, state


def selective_recurrent(x, dt, A, Bm, Cm, D,
                        init: Optional[jax.Array] = None):
    """The selective recurrence token by token: x and dt [B, T, C], A [N,
    C], Bm and Cm [B, T, N], D [C] -> (y [B, T, C] float32, the last state
    [B, N, C] float32). A prompt's form off a TPU, and what the kernels are
    tested against."""
    B, T, C = x.shape
    N = A.shape[0]
    state = jnp.zeros((B, N, C), jnp.float32) if init is None else init

    def one(state, t):
        y, state = selective_step(state, t[0], t[1], A, t[2], t[3], D)
        return state, y

    move = lambda a: jnp.moveaxis(a, 1, 0)      # noqa: E731
    state, ys = jax.lax.scan(one, state,
                             (move(x), move(dt), move(Bm), move(Cm)),
                             unroll=SELECTIVE_UNROLL)
    return jnp.moveaxis(ys, 0, 1), state
