"""Fused matmul+BatchNorm building blocks for 1x1 convolutions.

A per-tensor bytes model of the ResNet-50 training step (modelled,
never measured) puts BN passes at the largest share of its HBM
traffic, and XLA cannot fuse across the BN-stats reduction barrier.
These kernels implement the forward half for the 1x1 convs (2/3 of
ResNet-50's conv units; a 1x1 conv over NHWC is exactly a [B*H*W, Cin]
@ [Cin, Cout] matmul):

- matmul_stats:   y = x @ w, with per-channel sum/sumsq accumulated in
                  the kernel epilogue — the separate BN-stats read pass
                  over y never happens.
- bn_act_matmul:  y = act(norm(x)) @ w — the PRODUCER's BN-apply is
                  fused into the CONSUMER matmul's prologue, so the
                  normalized activation never reaches HBM (saves the
                  apply read+write passes).

Together these remove ~3 of the 6 modeled BN passes per conv unit
(bytes table: floor 95 -> ~81 ms, ceiling MFU 0.337 -> ~0.395 at
bs=256). Backward is the XLA reference implementation via custom_vjp
(rematerialized from the raw inputs — same bytes as the unfused
backward; fusing the backward is the remaining half of the line-item).

Reference analogue: none — the reference computes conv, BN-stats and
BN-apply as separate C++/cuDNN ops (batch_norm_op.cc, conv_op.cc); this
fusion is TPU-native ground. The kernels compile for the TPU unless a
caller passes `interpret=True` (the pallas interpreter: same kernel
bodies on CPU — how the tests run them; the model path never asks).
Like every pallas op here, the kernels require a single device or a
shard_map manual region (pallas_call has no GSPMD partitioning rule).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _block(n, cap):
    """Largest divisor of n that is <= cap (TPU-friendly caps are
    multiples of 128; inputs here are conv channel counts, powers of 2)."""
    b = min(n, cap)
    while n % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# matmul with stats epilogue
# ---------------------------------------------------------------------------


def _mm_stats_kernel(x_ref, w_ref, y_ref, ps_ref, pss_ref):
    # accumulation dtype rides on the stats refs (f32 normally; f64 under
    # the x64 parity rig, where interpret mode executes on CPU)
    y = jnp.dot(x_ref[...], w_ref[...],
                preferred_element_type=ps_ref.dtype)
    y_ref[...] = y.astype(y_ref.dtype)
    # per-(row-block, col-block) partial channel sums; finished by a tiny
    # [gm, 1, N] reduction outside the kernel (_finish_stats)
    ps_ref[...] = jnp.sum(y, axis=0, keepdims=True)
    pss_ref[...] = jnp.sum(y * y, axis=0, keepdims=True)


def _acc_dt(x):
    return jnp.promote_types(x.dtype, jnp.float32)


def _stats_out(M, N, bm, bn, y_dtype, acc):
    """out_specs/out_shape of (y, partial sums, partial sums of squares).
    The per-(row-block, col-block) partials are [gm, 1, N] with the row
    block squeezed: the kernel sees a (1, bn) tile whose second-to-last
    dim SPANS the array's, which is what the TPU lowering's (8, 128)
    block rule requires of a one-row block (a (1, bn) block of a
    [gm, N] array is refused)."""
    gm = M // bm
    part = pl.BlockSpec((None, 1, bn), lambda i, j: (i, 0, j))
    specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j)), part, part]
    shapes = [jax.ShapeDtypeStruct((M, N), y_dtype),
              jax.ShapeDtypeStruct((gm, 1, N), acc),
              jax.ShapeDtypeStruct((gm, 1, N), acc)]
    return specs, shapes


def _finish_stats(ps, pss, M):
    """Partial sums [gm, 1, N] -> (mean, biased var), one-pass form."""
    mean = jnp.sum(ps, axis=(0, 1)) / M
    var = jnp.maximum(jnp.sum(pss, axis=(0, 1)) / M - mean * mean, 0.0)
    return mean, var


def _mm_stats_pallas(x, w, interpret):
    M, K = x.shape
    K2, N = w.shape
    bm = _block(M, 512)
    bn = _block(N, 512)
    out_specs, out_shape = _stats_out(M, N, bm, bn, x.dtype, _acc_dt(x))
    y, ps, pss = pl.pallas_call(
        _mm_stats_kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, w)
    return (y,) + _finish_stats(ps, pss, M)


def _mm_stats_ref(x, w):
    """XLA reference: semantically what the kernel computes (promoted
    accumulation, one-pass E[y^2]-E[y]^2 stats)."""
    y32 = jnp.dot(x, w, preferred_element_type=_acc_dt(x))
    y = y32.astype(x.dtype)
    mean = jnp.mean(y32, axis=0)
    var = jnp.maximum(jnp.mean(y32 * y32, axis=0) - mean * mean, 0.0)
    return y, mean, var


def matmul_stats(x, w, interpret=False):
    """y = x @ w plus per-output-channel (mean, biased var), with the
    stats accumulated in the matmul's epilogue — the BN-stats pass over
    y never touches HBM. x: [M, K]; w: [K, N] -> (y [M,N], mean [N],
    var [N], both f32)."""
    return _matmul_stats(bool(interpret), x, w)


# custom_vjp takes positional args only; static flags lead
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_stats(interpret, x, w):
    return _mm_stats_pallas(x, w, interpret)


def _mm_stats_fwd(interpret, x, w):
    return _matmul_stats(interpret, x, w), (x, w)


def _mm_stats_bwd(interpret, res, cts):
    x, w = res
    _, pull = jax.vjp(_mm_stats_ref, x, w)
    return pull(cts)


_matmul_stats.defvjp(_mm_stats_fwd, _mm_stats_bwd)


# ---------------------------------------------------------------------------
# BN-apply (+activation) fused into the consumer matmul's prologue
# ---------------------------------------------------------------------------


def _bn_mm_kernel(x_ref, s_ref, b_ref, w_ref, y_ref, *, relu):
    xn = (x_ref[...].astype(s_ref.dtype) * s_ref[...]
          + b_ref[...])
    if relu:
        xn = jnp.maximum(xn, 0.0)
    y_ref[...] = jnp.dot(xn.astype(x_ref.dtype), w_ref[...],
                         preferred_element_type=s_ref.dtype
                         ).astype(y_ref.dtype)


def _bn_mm_pallas(x, scale, shift, w, relu, interpret):
    M, K = x.shape
    K2, N = w.shape
    bm = _block(M, 512)
    bn = _block(N, 512)
    return pl.pallas_call(
        functools.partial(_bn_mm_kernel, relu=relu),
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, K), lambda i, j: (0, 0)),
                  pl.BlockSpec((1, K), lambda i, j: (0, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x, scale.reshape(1, K), shift.reshape(1, K), w)


def _bn_mm_ref(x, scale, shift, w, relu):
    xn = x.astype(scale.dtype) * scale + shift
    if relu:
        xn = jnp.maximum(xn, 0.0)
    return jnp.dot(xn.astype(x.dtype), w,
                   preferred_element_type=scale.dtype).astype(x.dtype)


def bn_act_matmul(x, scale, shift, w, relu=True, interpret=False):
    """y = act(x * scale + shift) @ w, the normalization applied in the
    matmul prologue — the normalized tensor never reaches HBM. Callers
    fold BN into (scale, shift): scale = gamma * rsqrt(var + eps),
    shift = beta - mean * scale (both [K], f32). x: [M, K]; w: [K, N]."""
    return _bn_act_matmul(bool(relu), bool(interpret), x, scale, shift, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bn_act_matmul(relu, interpret, x, scale, shift, w):
    return _bn_mm_pallas(x, scale, shift, w, relu, interpret)


def _bn_mm_fwd(relu, interpret, x, scale, shift, w):
    return (_bn_act_matmul(relu, interpret, x, scale, shift, w),
            (x, scale, shift, w))


def _bn_mm_bwd(relu, interpret, res, ct):
    x, scale, shift, w = res
    _, pull = jax.vjp(
        lambda x, s, b, w: _bn_mm_ref(x, s, b, w, relu), x, scale,
        shift, w)
    return pull(ct)


_bn_act_matmul.defvjp(_bn_mm_fwd, _bn_mm_bwd)


# ---------------------------------------------------------------------------
# combined: BN-apply prologue + stats epilogue in one kernel
# ---------------------------------------------------------------------------


def _bn_mm_stats_kernel(x_ref, s_ref, b_ref, w_ref, y_ref, ps_ref,
                        pss_ref, *, relu):
    xn = x_ref[...].astype(s_ref.dtype) * s_ref[...] + b_ref[...]
    if relu:
        xn = jnp.maximum(xn, 0.0)
    y = jnp.dot(xn.astype(x_ref.dtype), w_ref[...],
                preferred_element_type=ps_ref.dtype)
    y_ref[...] = y.astype(y_ref.dtype)
    ps_ref[...] = jnp.sum(y, axis=0, keepdims=True)
    pss_ref[...] = jnp.sum(y * y, axis=0, keepdims=True)


def _bn_mm_stats_ref(x, scale, shift, w, relu):
    # stats from the PRE-downcast accumulator product, mirroring both
    # _mm_stats_ref and the kernel (which reduces the f32 `y` before
    # y_ref downcasts it): at bf16 the bwd must differentiate the same
    # stats the fwd computed, not stats of the already-rounded y
    # (ADVICE r5)
    xn = x.astype(scale.dtype) * scale + shift
    if relu:
        xn = jnp.maximum(xn, 0.0)
    y32 = jnp.dot(xn.astype(x.dtype), w,
                  preferred_element_type=_acc_dt(x))
    y = y32.astype(x.dtype)
    mean = jnp.mean(y32, axis=0)
    var = jnp.maximum(jnp.mean(y32 * y32, axis=0) - mean * mean, 0.0)
    return y, mean, var


def bn_act_matmul_stats(x, scale, shift, w, relu=True, interpret=False):
    """The full producer/consumer fusion: y = act(x*scale+shift) @ w with
    (mean, var) of y accumulated in the same kernel — the previous BN's
    apply AND this conv's stats pass both disappear from HBM traffic.
    This is ResNet's conv3 shape: bn2-apply+relu in the prologue, bn3
    stats in the epilogue."""
    return _bn_act_matmul_stats(bool(relu), bool(interpret), x, scale,
                                shift, w)


def _bn_mm_stats_pallas(x, scale, shift, w, relu, interpret):
    M, K = x.shape
    K2, N = w.shape
    bm = _block(M, 512)
    bn = _block(N, 512)
    out_specs, out_shape = _stats_out(M, N, bm, bn, x.dtype, _acc_dt(x))
    y, ps, pss = pl.pallas_call(
        functools.partial(_bn_mm_stats_kernel, relu=relu),
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, K), lambda i, j: (0, 0)),
                  pl.BlockSpec((1, K), lambda i, j: (0, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, scale.reshape(1, K), shift.reshape(1, K), w)
    return (y,) + _finish_stats(ps, pss, M)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bn_act_matmul_stats(relu, interpret, x, scale, shift, w):
    return _bn_mm_stats_pallas(x, scale, shift, w, relu, interpret)


def _bn_mm_stats_fwd(relu, interpret, x, scale, shift, w):
    return (_bn_act_matmul_stats(relu, interpret, x, scale, shift, w),
            (x, scale, shift, w))


def _bn_mm_stats_bwd(relu, interpret, res, cts):
    x, scale, shift, w = res
    _, pull = jax.vjp(
        lambda x, s, b, w: _bn_mm_stats_ref(x, s, b, w, relu), x, scale,
        shift, w)
    return pull(cts)


_bn_act_matmul_stats.defvjp(_bn_mm_stats_fwd, _bn_mm_stats_bwd)


def fold_bn(mean, var, gamma, beta, eps=1e-5):
    """(mean, var, gamma, beta) -> (scale, shift) for bn_act_matmul."""
    scale = gamma * jax.lax.rsqrt(var + eps)
    return scale, beta - mean * scale
