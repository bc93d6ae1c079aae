"""JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash, `config.json`): a
decoder-only transformer with multi-head LATENT attention (MLA), a dense
SwiGLU layer first and sigmoid-routed sparse experts with a shared expert
after it. One layer, as the configuration defines it (eps 1e-6, no biases):

    y        = RMSNorm(x)
    c_q      = RMSNorm(y W_qa)                      [q_rank 1536]
    q_h      = c_q W_qb -> heads x [q_nope 128 | q_rope 64]
    [c | kr] = y W_kva                              [kv_rank 512 | 64]
    c        = RMSNorm(c);  k_rope = RoPE(kr)       one rotary key, all heads
    q_rope_h = RoPE(q_rope_h)         interleaved pairs (2i, 2i+1), no scaling
    [k_nope_h | v_h] = c W_kvb -> heads x [128 | 128]
    a_h(t,s) = softmax_s<=t((q_nope_h . k_nope_h + q_rope_h . k_rope)
                            / sqrt(192))
    h        = x + concat_h(sum_s a_h v_h) W_o
    y        = RMSNorm(h)
    the first `dense_layers`:  out = h + (silu(y G) * (y U)) D   width 7168
    the others:  s = sigmoid(y W_r) in float32; top-8 of (s + b), b the
                 correction bias; w_e = s_e / (sum of the 8 s_e + 1e-20) x 2.5
                 out = h + sum_e w_e SwiGLU_e(y) + SwiGLU_shared(y)

then a final RMSNorm and an untied head. `n_group` = `topk_group` = 1: no
group-limited selection. No token is dropped, so a row's result depends on
that row alone.

What the cache holds is `c` after its norm and `k_rope` after its
rotation, nothing else: 512 + 64 values a token a layer for all 32 heads
(`JoyaiServe.stored`; serving/kv_cache.py says how they lie). The two
forms of the same attention:

- EXPANDED (`apply`, and a whole prompt's prefill): `c` goes through
  `W_kvb` to per-head keys and values, scores are 192 wide, values 128,
  through `pa.mha`. The expanded K and V live for one layer's attention
  and are never stored.
- ABSORBED (every read of the cache: the decode step, a prefill chunk, a
  verified span): `W_UK` (the key half of `W_kvb`) goes into the query,
  `q~_h = q_nope_h W_UK,h^T` [512], scores are `(q~_h . c + q_rope_h .
  k_rope) / sqrt(192)`, the context is `sum a c` [512] a head, and `W_UV`
  (the value half) takes it to `o_h` [128]. All heads read ONE row a token.

Not served: the multi-token-prediction module (`num_nextn_predict_layers`:
one more block whose head drafts a second token). It is a training loss
first; as a self-draft it waits for a step that yields more than one token
(ROADMAP M8).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from . import decoder as _decoder, moe as _moe
from .common import Params, rms as _rms, rms_norm as _rms_norm

ROPE_LANES = 128    # the pool that holds the 64-wide rotary key: one lane tile
SLICE_KEYS = 1024   # keys a chunk of a prompt slice's walk (`attend_slice`)
_MASKED = -1e30     # a score no softmax sees


@dataclasses.dataclass
class JoyaiConfig:
    vocab_size: int = 129280
    hidden: int = 2048
    layers: int = 40            # all of them, the leading dense ones too
    dense_layers: int = 1       # `first_k_dense_replace`
    heads: int = 32
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_dim: int = 7168       # the dense layers' width (`intermediate_size`)
    expert_dim: int = 768       # one expert's (`moe_intermediate_size`)
    n_experts: int = 256
    top_k: int = 8
    route_scale: float = 2.5
    max_len: int = 131072
    rope_theta: float = 32e6
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

    @staticmethod
    def tiny() -> "JoyaiConfig":
        return JoyaiConfig(vocab_size=512, hidden=64, layers=3,
                           dense_layers=1, heads=4, q_rank=48, kv_rank=32,
                           nope_dim=16, rope_dim=8, v_dim=16, dense_dim=96,
                           expert_dim=32, n_experts=8, top_k=2, max_len=128)

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def rope_inv_freq(self):
        """The rotary pairs' inverse frequencies where they are not the
        plain `theta^(-2i/d)` (a scaled RoPE: `models/xing4.py`'s YaRN);
        None here: `rope_scaling` is null."""
        return None

    @property
    def softmax_scale(self) -> float:
        """What the attention scores are multiplied by: `1/sqrt(192)`, and
        no mscale (no rope scaling)."""
        return 1.0 / math.sqrt(self.qk_dim)

    @property
    def lora_scales(self) -> Tuple[float, float]:
        """What the compressed query and the compressed kv are multiplied
        by AFTER their norms (a model that scales them by `sqrt(hidden /
        rank)`: `models/longcat.py`'s `mla_scale_q_lora` /
        `mla_scale_kv_lora`); 1 and 1 here: the norms' gains alone."""
        return 1.0, 1.0

    @property
    def routing(self) -> _moe.Routing:
        return _moe.Routing(self.n_experts, self.top_k, score="sigmoid",
                            bias=True, normalise=True,
                            scale=self.route_scale, shared=True)

    def serve_model(self) -> "JoyaiServe":
        """This configuration behind the interface the decode engine
        drives (models/decoder.py)."""
        return JoyaiServe(self)


# A layer's parameters carry the prefix `blk.` inside every function here;
# in the flat set the expert layers are stacked under `blk.` and the leading
# dense ones under `dense.` (`_lead_params` renames a slice).
_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,),
             "head.w": ("embed", "vocab")}
_ATTN_AXES = {
    "ln_in.scale": (None,), "ln_post.scale": (None,),
    "wq_a": ("embed", None), "q_norm.scale": (None,),
    "wq_b": (None, "heads"), "wkv_a": ("embed", None),
    "kv_norm.scale": (None,), "wkv_b": (None, "heads"),
    "wo": ("heads", "embed"),
}
_DENSE_AXES = {"mlp_gate": ("embed", "mlp"), "mlp_up": ("embed", "mlp"),
               "mlp_down": ("mlp", "embed")}
_EXPERT_AXES = {
    "router": ("embed", None), "router_bias": (None,),
    "w_gate": ("expert", "embed", "mlp"), "w_up": ("expert", "embed", "mlp"),
    "w_down": ("expert", "mlp", "embed"),
    "shared_gate": ("embed", "mlp"), "shared_up": ("embed", "mlp"),
    "shared_down": ("mlp", "embed"),
}
# how far the correction bias spreads: the 8th and 9th largest of 256
# sigmoid scores of unit-variance logits lie 0.007 apart in the mean, so a
# bias of this deviation changes the top-8 SET of about 4% of the tokens a
# layer (0.001: 9%, 0.005: 37%, 0.02: 88%) and leaves the others' alone
BIAS_STD = 0.0005
# how far a norm's gains lie from 1: a gain of 1 on an input whose RMS the
# init scales already hold near 1 (the compressed q and kv) makes that norm
# the identity, and a cache that stored `c` BEFORE its norm would read the
# same as one that stores it after
NORM_STD = 0.25
# how far a layer's routed experts lie apart: an expert's matrices are
# sqrt(1 - spread^2) of ONE draw the layer's experts share plus `spread` of
# the expert's own, at the plain deviation entry by entry (experts that
# began as copies of one dense MLP and moved apart: sparse upcycling). A
# token's eight then add up, the routed experts are the largest part of a
# layer's output, and WHICH of two near-tied experts a token is given
# matters as little as the spread. With every expert its own draw (1) the
# eight weigh 0.31 each, the 8th and 9th of 256 sigmoid scores lie 0.007
# apart whatever the router's deviation or the bias (PERF.md section 6,
# PR 31), bf16 rounding flips them for a tenth of the tokens a layer, and
# one flip moves the residual stream by a tenth: the bf16 model is then as
# far from the float32 one as a float8 model is, and no comparison of the
# two means anything.
EXPERT_SPREAD = 0.125


def init_layer(rng: jax.Array, cfg: JoyaiConfig, l) -> Params:
    """Layer `l` (counted over ALL layers) of `init(rng, cfg)` alone, in
    float32 and with the prefix `blk.`: every layer has a key of its own,
    so that a model whose float32 set does not fit the device can be made,
    and checked, one layer at a time. A dense layer if `l` (a Python int)
    is below `cfg.dense_layers`, an expert layer otherwise."""
    H, E = cfg.hidden, cfg.n_experts
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), l), 20))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def gains(n):
        return 1.0 + normal((n,), NORM_STD)

    def experts(shape, scale):
        own, base = normal(shape, scale), normal(shape[1:], scale)
        return math.sqrt(1.0 - EXPERT_SPREAD ** 2) * base \
            + EXPERT_SPREAD * own

    a = math.sqrt(1.0 / H)
    res = 1.0 / math.sqrt(2 * cfg.layers)   # the two residual outputs
    lp = {
        "blk.ln_in.scale": gains(H),
        "blk.wq_a": normal((H, cfg.q_rank), a),
        "blk.q_norm.scale": gains(cfg.q_rank),
        "blk.wq_b": normal((cfg.q_rank, cfg.heads * cfg.qk_dim),
                           math.sqrt(1.0 / cfg.q_rank)),
        "blk.wkv_a": normal((H, cfg.kv_rank + cfg.rope_dim), a),
        "blk.kv_norm.scale": gains(cfg.kv_rank),
        "blk.wkv_b": normal(
            (cfg.kv_rank, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
            math.sqrt(1.0 / cfg.kv_rank)),
        "blk.wo": normal((cfg.heads * cfg.v_dim, H),
                         math.sqrt(1.0 / (cfg.heads * cfg.v_dim)) * res),
        "blk.ln_post.scale": gains(H),
    }
    if isinstance(l, int) and l < cfg.dense_layers:
        D = cfg.dense_dim
        lp.update({
            "blk.mlp_gate": normal((H, D), a),
            "blk.mlp_up": normal((H, D), a),
            "blk.mlp_down": normal((D, H), math.sqrt(1.0 / D) * res),
        })
        return lp
    M = cfg.expert_dim
    down = math.sqrt(1.0 / M) * res
    lp.update({
        "blk.router": normal((H, E), a),
        "blk.router_bias": normal((E,), BIAS_STD),
        "blk.w_gate": experts((E, H, M), a),
        "blk.w_up": experts((E, H, M), a),
        "blk.w_down": experts((E, M, H), down),
        "blk.shared_gate": normal((H, M), a),
        "blk.shared_up": normal((H, M), a),
        "blk.shared_down": normal((M, H), down),
    })
    return lp


def init_top(rng: jax.Array, cfg: JoyaiConfig) -> Params:
    """The parameters of `init(rng, cfg)` outside the layers, in float32:
    embedding, final norm, head."""
    k_emb, k_head, k_norm = jax.random.split(jax.random.fold_in(rng, 0), 3)
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32) * 0.02,
        "ln_f.scale": 1.0 + NORM_STD * jax.random.normal(
            k_norm, (H,), jnp.float32),
        "head.w": jax.random.normal(k_head, (H, V), jnp.float32)
        * math.sqrt(1.0 / H),
    }


def init(rng: jax.Array, cfg: JoyaiConfig, dtype=jnp.float32,
         init_layer=init_layer, layer_axes=None) -> Tuple[Params, Dict]:
    """The dense layers are stacked under `dense.` and the expert layers
    under `blk.`, each on a leading axis, made one layer at a time and cast
    to `dtype` as each is made: the float32 set of a model too large for
    the device is never whole on it. A model that shares this block and
    adds parameters to a layer (`models/xing4.py`) hands over its own
    `init_layer` and the axes of what it adds (`layer_axes`)."""
    def cast(lp):
        return {k: v.astype(dtype) for k, v in lp.items()}

    params = cast(init_top(rng, cfg))
    dense = [cast(init_layer(rng, cfg, l)) for l in range(cfg.dense_layers)]
    for k in (dense[0] if dense else ()):
        params["dense." + k[4:]] = jnp.stack([lp[k] for lp in dense])
    params.update(jax.lax.map(
        lambda l: cast(init_layer(rng, cfg, l)),
        jnp.arange(cfg.dense_layers, cfg.layers, dtype=jnp.int32)))
    axes = dict(_TOP_AXES)
    for prefix, kind in (("dense.", _DENSE_AXES), ("blk.", _EXPERT_AXES)):
        axes.update({prefix + k: ("layer",) + a
                     for k, a in {**_ATTN_AXES, **kind,
                         **(layer_axes or {})}.items()})
    return params, axes


# Layer scopes, named as models/gpt.py names them, with this block's own
# parts nested INSIDE them so that a reduction by the shared names still
# adds up: `ln`; `qkv` (holding `mla_q`: the query's two projections and
# its norm, `mla_kv`: the compression and its norm, and `rope`);
# `attention` (holding `absorb`: W_UK into the query and W_UV onto the
# context, in the forms that read the cache); `proj`; `mlp` (holding
# `dense_mlp`, or models/moe.py's `router`, `moe_route`, `experts` and
# `shared_expert`); `head`. tests/test_layer_scopes.py holds the list.


def _rope(x, positions, theta: float, inv=None):
    """Rotary embedding of the trailing dimension of `x` [..., d] at
    `positions` (shaped like x's leading dimensions, or broadcastable to
    them): the INTERLEAVED convention, pair i is lanes (2i, 2i+1) and
    turns by position * theta^(-2i/d), or by position * `inv[i]` where the
    model scales its frequencies (`cfg.rope_inv_freq`, [d/2] float32);
    angles and rotation in float32."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv      # [..., d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@jax.named_scope("qkv")
def _qkv(lp, y, positions, cfg: JoyaiConfig):
    """(q `[..., heads*192]`, rotated; c `[..., 512]`, normalised; k_rope
    `[..., 64]`, rotated): the query and the two things a token stores."""
    lead = y.shape[:-1]
    q_scale, kv_scale = cfg.lora_scales

    def gains(name, by):    # a scale after a norm is a scale of its gains
        g = lp[name]
        return g if by == 1.0 else g.astype(jnp.float32) * by

    with jax.named_scope("mla_q"):
        cq = _rms(y @ lp["blk.wq_a"].astype(y.dtype),
                  gains("blk.q_norm.scale", q_scale), cfg.rms_eps)
        q = (cq @ lp["blk.wq_b"].astype(y.dtype)).reshape(
            lead + (cfg.heads, cfg.qk_dim))
    with jax.named_scope("mla_kv"):
        ckr = y @ lp["blk.wkv_a"].astype(y.dtype)
        c = _rms(ckr[..., :cfg.kv_rank],
                 gains("blk.kv_norm.scale", kv_scale), cfg.rms_eps)
    with jax.named_scope("rope"):
        inv = cfg.rope_inv_freq
        kr = _rope(ckr[..., cfg.kv_rank:], positions, cfg.rope_theta, inv)
        q = jnp.concatenate(
            [q[..., :cfg.nope_dim],
             _rope(q[..., cfg.nope_dim:], positions[..., None],
                   cfg.rope_theta, inv)], axis=-1)
    return q.reshape(lead + (-1,)), c, kr


def _expanded_attention(lp, q, c, kr, cfg: JoyaiConfig):
    """Causal attention of whole sequences in the expanded form: q `[B, T,
    heads*192]`, c `[B, T, 512]`, kr `[B, T, >=64]` -> `[B, T,
    heads*128]`."""
    from ..ops.pallas import attention as pa

    B, T = q.shape[:2]
    nh, dn, dv = cfg.heads, cfg.nope_dim, cfg.v_dim
    kv = (c @ lp["blk.wkv_b"].astype(c.dtype)).reshape(B, T, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(kr[:, :, None, :cfg.rope_dim],
                          (B, T, nh, cfg.rope_dim))], axis=-1)
    ctx = pa.mha(q.reshape(B, T, nh, cfg.qk_dim), k, kv[..., dn:],
                 causal=True, scale=cfg.softmax_scale)
    return ctx.reshape(B, T, nh * dv)


def _w_kvb(lp, cfg: JoyaiConfig, dtype):
    """(W_UK `[512, heads, 128]`, W_UV `[512, heads, 128]`) of `wkv_b`."""
    w = lp["blk.wkv_b"].astype(dtype).reshape(
        cfg.kv_rank, cfg.heads, cfg.nope_dim + cfg.v_dim)
    return w[..., :cfg.nope_dim], w[..., cfg.nope_dim:]


@jax.named_scope("absorb")
def _absorb_query(lp, q, cfg: JoyaiConfig):
    """q `[..., heads*192]` -> (q~ `[..., heads, 512]`: the no-position
    part through W_UK, q_rope `[..., heads, 64]`)."""
    q = q.reshape(q.shape[:-1] + (cfg.heads, cfg.qk_dim))
    w_uk, _ = _w_kvb(lp, cfg, q.dtype)
    return (jnp.einsum("...nd,cnd->...nc", q[..., :cfg.nope_dim], w_uk),
            q[..., cfg.nope_dim:])


@jax.named_scope("absorb")
def _absorb_context(lp, ctx, cfg: JoyaiConfig):
    """The latent context `[..., heads, 512]` through W_UV -> `[...,
    heads*128]`."""
    _, w_uv = _w_kvb(lp, cfg, ctx.dtype)
    out = jnp.einsum("...nc,cnd->...nd", ctx, w_uv)
    return out.reshape(out.shape[:-2] + (-1,))


@jax.named_scope("proj")
def _proj(lp, ctx, res=None):
    """The output projection, added to the residual stream `res` where one
    is given (a model whose residual path is its own adds it itself)."""
    out = ctx @ lp["blk.wo"].astype(ctx.dtype)
    return out if res is None else res + out


def _mlp(lp, y, cfg: JoyaiConfig, layer=None):
    """The block's second half by the layer's kind, which its parameters
    say: the dense SwiGLU, or the expert layer (`layer`: its index in the
    expert stacks, where `lp` holds those whole)."""
    if "blk.mlp_gate" in lp:
        with jax.named_scope("mlp"), jax.named_scope("dense_mlp"):
            return _moe.swiglu(y, lp["blk.mlp_gate"], lp["blk.mlp_up"],
                                lp["blk.mlp_down"]), None
    return _moe.expert_mlp(lp, y, cfg.routing, layer)


_EXPERTS = ("blk.w_gate", "blk.w_up", "blk.w_down")


def _lead_params(params: Params, cfg: JoyaiConfig):
    return [{"blk." + k[6:]: v[i] for k, v in params.items()
             if k.startswith("dense.")} for i in range(cfg.dense_layers)]


def _layer_params(params: Params) -> Params:
    return {k: v for k, v in params.items() if k.startswith("blk.")}


class JoyaiServe(_decoder.ServeModel):
    """The block for the serve programs (models/decoder.py): a latent
    cache, attention in two forms, a dense layer before the expert ones."""

    def __init__(self, cfg: JoyaiConfig):
        self.cfg = cfg
        self.layers, self.heads = cfg.layers, cfg.heads
        self.head_dim = cfg.qk_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len
        # the rotary key's pool is whole lane tiles (serving/kv_cache.py)
        self.rope_lanes = -(-cfg.rope_dim // ROPE_LANES) * ROPE_LANES

    @property
    def stored(self):
        return (self.cfg.kv_rank, self.rope_lanes)

    def lead_params(self, params):
        return _lead_params(params, self.cfg)

    def layer_params(self, params):
        # the expert stacks stay whole: `expert_mlp` addresses them in place
        return {k: v for k, v in _layer_params(params).items()
                if k not in _EXPERTS}

    def embed(self, params, ids, positions):
        return params["wte.w"][ids]     # positions enter in `qkv` (RoPE)

    def norm_attn(self, lp, h):
        return _rms_norm(h, lp["blk.ln_in.scale"], self.cfg.rms_eps)

    def qkv(self, lp, y, positions):
        q, c, kr = _qkv(lp, y, positions, self.cfg)
        pad = [(0, 0)] * (kr.ndim - 1) \
            + [(0, self.rope_lanes - self.cfg.rope_dim)]
        return q, c, jnp.pad(kr, pad)

    def attend_prompt(self, lp, q, k, v):
        return _expanded_attention(lp, q, k, v, self.cfg)

    def attend_cached(self, lp, q, keys, vals, pos):
        cfg = self.cfg
        ql, qr = _absorb_query(lp, q, cfg)          # [S, W, heads, 512|64]
        m = keys.shape[1]
        scores = (jnp.einsum("swnc,smc->swnm", ql, keys)
                  + jnp.einsum("swnr,smr->swnm", qr,
                               vals[..., :cfg.rope_dim])) \
            * cfg.softmax_scale
        mask = jnp.arange(m, dtype=jnp.int32)[None, None, :] \
            <= pos[:, :, None]
        scores = jnp.where(mask[:, :, None, :], scores, -1e9)
        att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        ctx = jnp.einsum("swnm,smc->swnc", att.astype(keys.dtype), keys)
        return _absorb_context(lp, ctx, cfg)

    def paged_route(self, x, k_pool, v_pool):
        from ..ops.pallas import paged_attention as pa

        return "paged_latent" \
            if pa.use_paged_latent(x, k_pool, v_pool, self.heads) else None

    def attend_paged(self, lp, q, k_pool, v_pool, layer, block_tables,
                     positions):
        from ..ops.pallas import paged_attention as pa

        cfg = self.cfg
        ql, qr = _absorb_query(lp, q, cfg)          # [S, heads, 512|64]
        qr = jnp.pad(qr, [(0, 0), (0, 0),
                          (0, self.rope_lanes - cfg.rope_dim)])
        ctx = pa.paged_latent_attention(
            ql, qr, k_pool, v_pool, layer, block_tables, positions,
            scale=cfg.softmax_scale)
        return _absorb_context(lp, ctx, cfg)

    def attend_slice(self, lp, q, k_pool, v_pool, rated, layer, block_table,
                     start, block_size):
        """One slice of a prompt against the latent cache so far, in the
        EXPANDED form: the keys are walked in chunks of `SLICE_KEYS` tokens
        up to the slice's own, each chunk's `c` and rotary key gathered
        through the table, taken through `W_kvb` to per-head keys and
        values (never stored) and met by all the slice's queries under an
        online softmax: float32 scores of `heads x C x SLICE_KEYS` whatever
        the prompt's length. Expanded because a prompt's attention is
        compute-bound: a (query, key) pair of a head costs 192 + 128
        multiply-adds here against 576 + 512 absorbed, and expanding a
        chunk again for every later slice adds a fifth of that at 12k
        tokens (PERF.md section 6, PR 45)."""
        from ..serving import kv_cache as kvc

        cfg = self.cfg
        f32 = jnp.float32
        nh, dn, dv, dr = cfg.heads, cfg.nope_dim, cfg.v_dim, cfg.rope_dim
        C = q.shape[1]
        chunk = min(SLICE_KEYS, C)
        if C % chunk or chunk % block_size:
            raise ValueError(
                f"a slice of {C} queries walks its keys in whole chunks of "
                f"{chunk} tokens of whole blocks of {block_size}")
        per_chunk = chunk // block_size
        qh = q[0].reshape(C, nh, cfg.qk_dim)
        t = start + jnp.arange(C, dtype=jnp.int32)
        w_kvb = lp["blk.wkv_b"].astype(q.dtype)

        def one(i, carry):
            m, l, acc = carry
            blocks = jax.lax.dynamic_slice_in_dim(
                block_table, i * per_chunk, per_chunk)[None]
            c = kvc.gather_kv(k_pool, layer, blocks)[0]     # [chunk, 512]
            kr = kvc.gather_kv(v_pool, layer, blocks)[0][:, :dr]
            kv = (c @ w_kvb).reshape(chunk, nh, dn + dv)
            sc = (jnp.einsum("qnd,knd->nqk", qh[..., :dn], kv[..., :dn],
                             preferred_element_type=f32)
                  + jnp.einsum("qnr,kr->nqk", qh[..., dn:], kr,
                               preferred_element_type=f32)) \
                * cfg.softmax_scale
            tok = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
            ok = (tok[None, :] <= t[:, None])[None]         # [1, C, chunk]
            sc = jnp.where(ok, sc, _MASKED)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                "nqk,knd->nqd", p.astype(kv.dtype), kv[..., dn:],
                preferred_element_type=f32)
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, (start + C) // chunk, one,
            (jnp.full((nh, C, 1), _MASKED, f32), jnp.zeros((nh, C, 1), f32),
             jnp.zeros((nh, C, dv), f32)))
        ctx = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        return ctx.transpose(1, 0, 2).reshape(1, C, nh * dv), rated

    def proj(self, lp, ctx, res):
        return _proj(lp, ctx, res)

    def norm_mlp(self, lp, h):
        return _rms_norm(h, lp["blk.ln_post.scale"], self.cfg.rms_eps)

    def mlp(self, lp, y, params, l):
        if "blk.mlp_gate" not in lp:    # an expert layer: its stacks, whole
            lp = dict(lp, **{k: params[k] for k in _EXPERTS})
        return _mlp(lp, y, self.cfg, layer=l - self.cfg.dense_layers)

    def head(self, params, x, prev_ids, eos_id):
        return _decoder.rms_head(params, x, prev_ids, eos_id,
                                 self.cfg.rms_eps)

    def step_facts(self, stats) -> Dict:
        return _moe.step_facts(stats)


def _block(lp, x, positions, cfg: JoyaiConfig):
    """One block of the full forward pass, x [B, T, hidden]."""
    y = _rms_norm(x, lp["blk.ln_in.scale"], cfg.rms_eps)
    q, c, kr = _qkv(lp, y, positions, cfg)
    with jax.named_scope("attention"):
        ctx = _expanded_attention(lp, q, c, kr, cfg)
    x = shard(_proj(lp, ctx, x), ("batch", "seq", "embed"))
    y = _rms_norm(x, lp["blk.ln_post.scale"], cfg.rms_eps)
    out, _ = _mlp(lp, y, cfg)
    return shard(x + out, ("batch", "seq", "embed"))


def apply(params: Params, cfg: JoyaiConfig, ids: jax.Array) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab], attention in the expanded
    form."""
    B, T = ids.shape
    adt = jnp.dtype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = params["wte.w"][ids].astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    with jax.named_scope("layers"):
        for lp in _lead_params(params, cfg):
            x = _block(lp, x, positions, cfg)
        x, _ = jax.lax.scan(
            lambda h, lp: (_block(lp, h, positions, cfg), None), x,
            _layer_params(params))
    with jax.named_scope("head"):
        x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps)
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))
