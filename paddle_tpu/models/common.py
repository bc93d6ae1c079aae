"""Shared building blocks for the JAX-native model zoo.

Params are flat dicts {name: array}; logical sharding axes are returned
alongside as {name: (logical axes...)} consumed by
parallel.sharding.shard_params_spec. This mirrors how the reference keeps
parameters in a Scope keyed by name (framework/scope.h) rather than nested
module trees — and keeps checkpoint compatibility with the Program path
trivial (same flat names).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]
ParamAxes = Dict[str, Tuple[Optional[str], ...]]


class ParamStore:
    """Accumulates params + logical axes during init."""

    def __init__(self, rng: jax.Array, dtype=jnp.float32):
        self.rng = rng
        self.dtype = dtype
        self.params: Params = {}
        self.axes: ParamAxes = {}

    def next_rng(self) -> jax.Array:
        self.rng, k = jax.random.split(self.rng)
        return k

    def add(self, name: str, value: jax.Array, axes: Tuple[Optional[str], ...]):
        assert name not in self.params, f"duplicate param {name}"
        assert value.ndim == len(axes), (name, value.shape, axes)
        self.params[name] = value
        self.axes[name] = axes
        return value

    def dense(self, name: str, d_in: int, d_out: int,
              axes=("embed", "mlp"), bias: bool = True,
              init_scale: Optional[float] = None):
        scale = init_scale if init_scale is not None else math.sqrt(2.0 / (d_in + d_out))
        w = jax.random.normal(self.next_rng(), (d_in, d_out), self.dtype) * scale
        self.add(f"{name}.w", w, axes)
        if bias:
            self.add(f"{name}.b", jnp.zeros((d_out,), self.dtype), (axes[1],))

    def layer_norm(self, name: str, dim: int, axis: Optional[str] = None):
        self.add(f"{name}.scale", jnp.ones((dim,), self.dtype), (axis,))
        self.add(f"{name}.bias", jnp.zeros((dim,), self.dtype), (axis,))

    def embedding(self, name: str, vocab: int, dim: int,
                  axes=("vocab", "embed"), scale: float = 0.02):
        w = jax.random.normal(self.next_rng(), (vocab, dim), self.dtype) * scale
        self.add(f"{name}.w", w, axes)

    def conv(self, name: str, kh: int, kw: int, cin: int, cout: int,
             axes=(None, None, None, "conv_out")):
        fan_in = kh * kw * cin
        w = jax.random.normal(self.next_rng(), (kh, kw, cin, cout),
                              self.dtype) * math.sqrt(2.0 / fan_in)
        self.add(f"{name}.w", w, axes)

    def bn(self, name: str, dim: int):
        self.add(f"{name}.scale", jnp.ones((dim,), self.dtype), (None,))
        self.add(f"{name}.bias", jnp.zeros((dim,), self.dtype), (None,))
        # running stats are non-trainable state, kept in the same dict with
        # a marker prefix (filtered out of the optimizer by is_trainable)
        self.add(f"{name}.mean", jnp.zeros((dim,), jnp.float32), (None,))
        self.add(f"{name}.var", jnp.ones((dim,), jnp.float32), (None,))


def is_trainable(name: str) -> bool:
    return not (name.endswith(".mean") or name.endswith(".var"))


def dense(params: Params, name: str, x: jax.Array, act=None) -> jax.Array:
    w = params[f"{name}.w"]
    y = x @ w.astype(x.dtype)
    b = params.get(f"{name}.b")
    if b is not None:
        y = y + b.astype(y.dtype)
    if act is not None:
        y = act(y)
    return y


@jax.named_scope("ln")
def raw_layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                   eps: float = 1e-12) -> jax.Array:
    # compute in fp32 for stability under bf16 activations
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(params: Params, name: str, x: jax.Array, eps=1e-12) -> jax.Array:
    return raw_layer_norm(x, params[f"{name}.scale"], params[f"{name}.bias"],
                          eps)


def rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last dimension, computed in float32."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("ln")
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """`rms` as a block's norm (the layer scope `ln`)."""
    return rms(x, scale, eps)


def rope_half(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of `x` [..., heads, head_dim] at `positions` [...]:
    the rotate-half convention over the whole head dimension, angles and
    rotation in float32."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def dropout(rng: Optional[jax.Array], x: jax.Array, rate: float,
            deterministic: bool) -> jax.Array:
    if deterministic or rate == 0.0 or rng is None:
        return x
    with jax.named_scope("dropout"):
        # the barrier keeps the generator out of its readers' fusions: XLA
        # takes threefry for a cheap elementwise producer and would run it
        # again inside every fusion that reads the mask, forward and backward
        keep = jax.lax.optimization_barrier(
            jax.random.bernoulli(rng, 1.0 - rate, x.shape))
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def conv2d_nhwc(x, w, stride=1, padding="SAME"):
    """NHWC conv with HWIO weights — the shared TPU-native conv layout
    (resnet/lenet carry local variants pending consolidation)."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def maxpool2x2_nhwc(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


# -- INT8 serving path (reference capability: contrib/float16's low-
#    precision inference + mkldnn INT8 kernels; TPU-native form: int8
#    MXU convs with per-output-channel weight scales + dynamic per-
#    tensor activation scales) ---------------------------------------------


def quantize_conv_weights_int8(params: Params) -> Params:
    """Per-output-channel symmetric int8 for every 4-D HWIO conv weight
    '*.w'; adds '<k>@scale' [O] and leaves everything else untouched.
    The result feeds the same model apply(): conv helpers dispatch on
    the weight dtype."""
    out = dict(params)
    for k, v in params.items():
        if k.endswith(".w") and getattr(v, "ndim", 0) == 4:
            w = jnp.asarray(v, jnp.float32)
            amax = jnp.max(jnp.abs(w), axis=(0, 1, 2))
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            out[k] = jnp.clip(jnp.round(w / scale), -127,
                              127).astype(jnp.int8)
            out[k + "@scale"] = scale.astype(jnp.float32)
    return out


def conv2d_nhwc_int8(x, wq, w_scale, stride=1, padding="SAME"):
    """int8 x int8 -> int32 MXU conv; activation quantized dynamically
    (per-tensor abs-max), dequantized per output channel. Returns f32."""
    xf = x.astype(jnp.float32)
    xs = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-8) / 127.0
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    acc = jax.lax.conv_general_dilated(
        xq, wq, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (xs * w_scale.reshape(1, 1, 1, -1))


def conv2d_nhwc_auto(params: Params, name: str, x, stride=1,
                     padding="SAME"):
    """The dtype-dispatching conv the model zoo shares: int8 weights
    (from quantize_conv_weights_int8) take the int8 MXU path, anything
    else the plain bf16/f32 conv. Output in x.dtype either way."""
    w = params[f"{name}.w"]
    if w.dtype == jnp.int8:
        return conv2d_nhwc_int8(
            x, w, params[f"{name}.w@scale"], stride, padding
        ).astype(x.dtype)
    return conv2d_nhwc(x, w.astype(x.dtype), stride, padding)
