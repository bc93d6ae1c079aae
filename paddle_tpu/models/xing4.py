"""Xing4.0-29B-A4B (XingChen-AGI/Xing4.0-29B-A4B, `config.json`,
`model_type` xing4_0): DeepSeek-V3's block (multi-head LATENT attention, two
dense SwiGLU layers first, then top-4 of 64 sigmoid-routed experts with a
shared expert: `models/joyai.py` holds that block and this module REUSES it)
with two things of its own: YaRN on the rotary lanes, and a residual path of
`hc_mult` = 4 streams mixed by manifold-constrained hyper-connections (mHC,
arXiv:2512.24880; hyper-connections arXiv:2409.19606).

The residual path. A row carries `X` in R^{n x C} (n = 4 streams of C =
3584), here as ONE vector of n*C lanes, stream i the lanes `i*C ..
(i+1)*C - 1` (`vec(X)`; `[n, C]` would pad 4 sublanes to a tile of 16 in
bf16, four times the bytes of a prompt slice's streams). The embedding is
copied into all n streams (`widen`); after the last block the streams are
summed (`narrow`), then the final RMSNorm and the untied head. Each block
has two sub-layers F (attention with `ln_in`, then the dense MLP or the
expert layer with `ln_post`), each wrapped the same way with its OWN
parameters `phi` `[2n + n^2, n*C]` (Phi transposed: its 24 columns lie along
sublanes, `[n*C, 24]` would pad 24 lanes to 128), `b_pre`, `b_post` `[n]`,
`b_res` `[n, n]` and three scalars `a` = (a_pre, a_post, a_res):

    x^      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)        float32, no gain
    [p|q|R] = x^ Phi                                        4 | 4 | 16 columns
    H_pre   = sigmoid(a_pre p + b_pre)                      [n]
    H_post  = 2 sigmoid(a_post q + b_post)                  [n]
    A       = clip(a_res mat(R) + b_res, -30, 30)           [n, n]
    M       = exp(A);  `hc_sinkhorn_iters` (20) times:
              M <- M / (column sums + hc_eps), then M <- M / (row sums + hc_eps)
    H_res   = M                       doubly stochastic, as far as 20 rounds get
    u       = H_pre X                                       [C]: F's input
    y       = F(RMSNorm(u))
    X'      = H_res X + H_post^T y                          [n, C]

The maps are per token: a row's result depends on that row alone. They are
computed in float32 over the served dtype's streams (x^ is rounded to the
streams' dtype for the one product with Phi, whose result leaves the matmul
in float32, as a router's logits do). All 20 rounds run, unrolled: XLA may
fuse them, nothing shortens them, and `mhc_col_err` (the largest |column sum
of H_res - 1| of a decode step; rows are exact after the last row pass)
says in every step record how far they got.

YaRN (DeepSeek-V3's convention) on the 64 rotary lanes: pair i has `f_i =
theta^(-2i/64)`; `low = floor(64 ln(L0 / (beta_fast 2 pi)) / (2 ln theta))`
= 10, `high = ceil(64 ln(L0 / (beta_slow 2 pi)) / (2 ln theta))` = 23, `g_i
= clip((i - low) / (high - low), 0, 1)`, `inv_freq_i = (1 - g_i) f_i + g_i
f_i / factor`, fixed for every sequence length (`yarn_inv_freq`); with `m(a)
= 0.1 a ln(factor) + 1`, cos and sin are multiplied by `m(mscale) /
m(mscale_all_dim)` = 1 (a configuration where that is not 1 is refused) and
the softmax scale is `m(mscale_all_dim)^2 / sqrt(192)` = 2.0047 / sqrt(192).

Layer scopes: `mhc` is a SIBLING of `ln` / `qkv` / `attention` / `proj` /
`mlp` at the layer's level and holds `mhc_map` (the RMS, the product with
Phi, the sigmoids, Sinkhorn), `mhc_pre` (the mix in) and `mhc_post` (the
remix and the spread); tests/test_layer_scopes.py holds the list.

Not served: the multi-token-prediction module (`num_nextn_predict_layers`
1), as in `models/joyai.py` (ROADMAP M8).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import shard
from . import joyai as _joyai, moe as _moe
from .common import Params, rms_norm as _rms_norm

# how the seeded maps are drawn, so that every one of them MATTERS (the
# paper's own start, a = 0.01, makes H_res a constant matrix and a test
# blind): Phi entries normal / sqrt(n*C), so that p, q and R are unit
# normal over tokens; the three scalars 1 + 0.1 normal; `b_pre` and `b_post`
# 0.5 normal, so that H_pre lies in 0.27-0.73 and H_post in 0.54-1.46 for
# two thirds of the (token, stream) pairs; `b_res` = 2 I + 0.3 normal, so
# that exp(A) has a diagonal of e^2 against 1 and H_res a diagonal of about
# 0.6-0.7 where the identity has 1 and the uniform matrix 0.25. (A wider
# `b_pre` / `b_post`, 1.5, was tried on the chip: H_res = I then reads 4.3
# on the cell's statistic for 1.2, but the bf16 program's own rounding 0.51
# for 0.10-0.33, and a stream dropped from the final sum stays under both:
# PERF.md section 6, PR 45.)
A_STD = 0.1
B_STD = 0.5
RES_DIAG = 2.0
RES_STD = 0.3


def yarn_inv_freq(dim: int, theta: float, factor: float, orig_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The rotary pairs' inverse frequencies `[dim/2]` float32 under YaRN
    in DeepSeek-V3's convention: pairs that turn more than `beta_fast`
    times over the original length keep their frequency, pairs that turn
    less than `beta_slow` times are slowed by `factor`, a linear ramp
    between; the same for every sequence length."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def pair_of(turns):     # the pair that turns `turns` times over orig_len
        return dim * math.log(orig_len / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    g = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return ((1.0 - g) * f + g * f / factor).astype(np.float32)


def yarn_mscale(factor: float, a: float) -> float:
    """`m(a) = 0.1 a ln(factor) + 1` (1 where nothing is scaled)."""
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


@dataclasses.dataclass
class Xing4Config(_joyai.JoyaiConfig):
    vocab_size: int = 131072
    hidden: int = 3584
    layers: int = 40
    dense_layers: int = 2
    heads: int = 32
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_dim: int = 9216
    expert_dim: int = 1024
    n_experts: int = 64
    top_k: int = 4
    route_scale: float = 2.0
    max_len: int = 262144
    rope_theta: float = 10000.0
    # `rope_scaling` (type yarn)
    rope_factor: float = 64.0
    rope_orig_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the residual streams and their maps
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0     # `mhc_h_res_clamp_min`
    hc_clamp_max: float = 30.0
    # tokens a slice of the prefill program's walk over a prompt
    prompt_slice: int = 2048

    def __post_init__(self):
        gain = yarn_mscale(self.rope_factor, self.rope_mscale) \
            / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        if abs(gain - 1.0) > 1e-12:
            raise ValueError(
                "YaRN with mscale != mscale_all_dim multiplies cos and sin "
                f"by {gain}: the rotation here applies no such gain")

    @staticmethod
    def tiny() -> "Xing4Config":
        return Xing4Config(vocab_size=512, hidden=64, layers=4,
                           dense_layers=2, heads=4, q_rank=48, kv_rank=32,
                           nope_dim=16, rope_dim=8, v_dim=16, dense_dim=96,
                           expert_dim=32, n_experts=8, top_k=2, max_len=128,
                           rope_factor=8.0, rope_orig_len=16,
                           prompt_slice=16)

    @property
    def rope_inv_freq(self):
        return yarn_inv_freq(self.rope_dim, self.rope_theta,
                             self.rope_factor, self.rope_orig_len,
                             self.rope_beta_fast, self.rope_beta_slow)

    @property
    def softmax_scale(self) -> float:
        return yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2 \
            / math.sqrt(self.qk_dim)

    def serve_model(self) -> "Xing4Serve":
        return Xing4Serve(self)


SUB_LAYERS = ("attn", "mlp")
_HC_AXES = {f"hc_{w}.{k}": a for w in SUB_LAYERS for k, a in (
    ("phi", (None, None)), ("a", (None,)), ("b_pre", (None,)),
    ("b_post", (None,)), ("b_res", (None, None)))}


def init_layer(rng: jax.Array, cfg: Xing4Config, l) -> Params:
    """Layer `l` of `init(rng, cfg)` alone, float32, prefix `blk.`: the
    shared block's (`joyai.init_layer`, by `l` a dense or an expert layer)
    and the maps of its two sub-layers, from a key of their own."""
    lp = _joyai.init_layer(rng, cfg, l)
    n, width = cfg.hc_mult, cfg.hc_mult * cfg.hidden
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 2), l), 10))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    for which in SUB_LAYERS:
        p = f"blk.hc_{which}."
        lp.update({
            p + "phi": normal((2 * n + n * n, width), 1.0 / math.sqrt(width)),
            p + "a": 1.0 + normal((3,), A_STD),
            p + "b_pre": normal((n,), B_STD),
            p + "b_post": normal((n,), B_STD),
            p + "b_res": RES_DIAG * jnp.eye(n, dtype=jnp.float32)
            + normal((n, n), RES_STD),
        })
    return lp


init_top = _joyai.init_top


def init(rng: jax.Array, cfg: Xing4Config, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """As `joyai.init`: the dense layers stacked under `dense.`, the expert
    layers under `blk.`, a layer at a time and cast as each is made."""
    return _joyai.init(rng, cfg, dtype, init_layer=init_layer,
                       layer_axes=_HC_AXES)


# -- the maps ----------------------------------------------------------------


def sinkhorn(m, iters: int, eps: float):
    """`iters` rounds of (columns, then rows) on m `[n, n, ...]` (entry
    (i, j) of a row's matrix at `m[i, j]`, the rows of the batch on the
    trailing axes): every round runs, unrolled."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


@jax.named_scope("mhc_map")
def mhc_maps(hp, x, cfg: Xing4Config):
    """The three maps of one sub-layer for the carried rows x `[..., n*C]`,
    from its parameters `hp` (`phi`, `a`, `b_pre`, `b_post`, `b_res`), in
    float32 with the ROWS ON THE TRAILING AXES (a map's 4 or 16 numbers a
    row would fill a lane tile each otherwise): (H_pre `[n, ...]`, H_post
    `[n, ...]`, H_res `[n, n, ...]`, the largest |column sum of H_res - 1|
    over the rows)."""
    f32 = jnp.float32
    n = cfg.hc_mult
    lead = x.shape[:-1]
    xf = x.astype(f32).reshape(-1, x.shape[-1])
    xhat = xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.hc_eps)
    z = jax.lax.dot_general(
        hp["phi"].astype(x.dtype), xhat.astype(x.dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=f32)  # [24, rows]
    a = hp["a"].astype(f32)
    pre = jax.nn.sigmoid(a[0] * z[:n] + hp["b_pre"].astype(f32)[:, None])
    post = 2.0 * jax.nn.sigmoid(
        a[1] * z[n:2 * n] + hp["b_post"].astype(f32)[:, None])
    logits = a[2] * z[2 * n:].reshape(n, n, -1) \
        + hp["b_res"].astype(f32)[:, :, None]
    res = sinkhorn(
        jnp.exp(jnp.clip(logits, cfg.hc_clamp_min, cfg.hc_clamp_max)),
        cfg.hc_sinkhorn_iters, cfg.hc_eps)
    err = jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0))
    return (pre.reshape((n,) + lead), post.reshape((n,) + lead),
            res.reshape((n, n) + lead), err)


def _streams(x, n: int):
    """The n streams of the carried rows x `[..., n*C]`, float32."""
    C = x.shape[-1] // n
    return [x[..., i * C:(i + 1) * C].astype(jnp.float32) for i in range(n)]


@jax.named_scope("mhc_pre")
def mhc_pre(x, pre):
    """u = H_pre X: the sub-layer's input `[..., C]` in x's dtype."""
    xs = _streams(x, pre.shape[0])
    return sum(pre[i][..., None] * xs[i] for i in range(len(xs))) \
        .astype(x.dtype)


@jax.named_scope("mhc_post")
def mhc_post(x, y, post, res):
    """X' = H_res X + H_post^T y, `[..., n*C]` in x's dtype."""
    xs = _streams(x, post.shape[0])
    yf = y.astype(jnp.float32)
    return jnp.concatenate(
        [sum(res[i, j][..., None] * xs[j] for j in range(len(xs)))
         + post[i][..., None] * yf for i in range(len(xs))],
        axis=-1).astype(x.dtype)


def _hc(lp, which: str):
    p = f"blk.hc_{which}."
    return {k[len(p):]: v for k, v in lp.items() if k.startswith(p)}


def res_in(lp, x, which: str, cfg: Xing4Config):
    """Into sub-layer `which`: (u `[..., C]`, what `res_out` takes)."""
    with jax.named_scope("mhc"):
        pre, post, res, err = mhc_maps(_hc(lp, which), x, cfg)
        return mhc_pre(x, pre), (x, post, res, err)


def res_out(kept, y):
    x, post, res, _ = kept
    with jax.named_scope("mhc"):
        return mhc_post(x, y, post, res)


def widen(x, n: int):
    """The embedding copied into all n streams."""
    return jnp.tile(x, (1,) * (x.ndim - 1) + (n,))


def narrow(x, n: int):
    """The n streams summed."""
    return sum(_streams(x, n)).astype(x.dtype)


class Xing4Serve(_joyai.JoyaiServe):
    """The block for the serve programs: `JoyaiServe`'s latent cache and
    attention in its three forms (the rotary frequencies and the softmax
    scale are the configuration's), a prompt walked in slices, and the
    residual path of n streams."""

    def __init__(self, cfg: Xing4Config):
        super().__init__(cfg)
        self.prompt_slice = int(cfg.prompt_slice)

    def widen(self, params, x):
        return widen(x, self.cfg.hc_mult)

    def narrow(self, params, x):
        return narrow(x, self.cfg.hc_mult)

    def res_in(self, lp, h, which):
        return res_in(lp, h, which, self.cfg)

    def res_out(self, lp, kept, out, which):
        if which == "attn":
            out = _joyai._proj(lp, out)
        return res_out(kept, out)

    def res_counters(self, stats, *kept):
        err = kept[0][3]
        for k in kept[1:]:
            err = jnp.maximum(err, k[3])
        return dict(stats or {}, mhc_col_err=err)

    def describe(self) -> Dict:
        cfg = self.cfg
        return {"residual_streams": cfg.hc_mult,
                "sinkhorn_iters": cfg.hc_sinkhorn_iters,
                "carried_lanes": cfg.hc_mult * cfg.hidden}

    def step_facts(self, stats) -> Dict:
        """`experts_hit` / `expert_load_max` of the expert layers, and
        `mhc_col_err`: the largest |column sum of H_res - 1| over the
        step's rows (idle slots included: the device computes them all),
        sub-layers and layers, the leading dense ones too."""
        parts = list(stats["lead"]) + [stats["stack"]] \
            if "stack" in stats else [stats]
        facts = {"mhc_col_err": max(
            float(np.max(p["mhc_col_err"])) for p in parts)}
        if "experts_hit" in parts[-1]:
            facts.update(_moe.step_facts(parts[-1]))
        return facts


def _block(lp, x, positions, cfg: Xing4Config):
    """One block of the full forward pass, x `[B, T, n*C]`."""
    u, kept = res_in(lp, x, "attn", cfg)
    y = _rms_norm(u, lp["blk.ln_in.scale"], cfg.rms_eps)
    q, c, kr = _joyai._qkv(lp, y, positions, cfg)
    with jax.named_scope("attention"):
        ctx = _joyai._expanded_attention(lp, q, c, kr, cfg)
    x = shard(res_out(kept, _joyai._proj(lp, ctx)), ("batch", "seq", None))
    u, kept = res_in(lp, x, "mlp", cfg)
    y = _rms_norm(u, lp["blk.ln_post.scale"], cfg.rms_eps)
    out, _ = _joyai._mlp(lp, y, cfg)
    return shard(res_out(kept, out), ("batch", "seq", None))


def apply(params: Params, cfg: Xing4Config, ids: jax.Array) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab], attention in the expanded
    form."""
    B, T = ids.shape
    adt = jnp.dtype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = widen(params["wte.w"][ids].astype(adt), cfg.hc_mult)
    with jax.named_scope("layers"):
        for lp in _joyai._lead_params(params, cfg):
            x = _block(lp, x, positions, cfg)
        x, _ = jax.lax.scan(
            lambda h, lp: (_block(lp, h, positions, cfg), None), x,
            _joyai._layer_params(params))
    with jax.named_scope("head"):
        x = _rms_norm(narrow(x, cfg.hc_mult), params["ln_f.scale"],
                      cfg.rms_eps)
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))
