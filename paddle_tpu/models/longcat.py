"""LongCat-Flash-Chat (meituan-longcat/LongCat-Flash-Chat, `config.json`): a
decoder-only transformer whose LAYER holds two latent-attention sub-layers
and two dense SwiGLU MLPs, with a sparse expert layer as a SHORTCUT across
the second sub-block ("shortcut-connected MoE"), and a router some of whose
outputs are ZERO-COMPUTE experts. One layer, `x` its input (all norms
RMSNorm with eps 1e-5 and a gain, no biases):

    a_0   = x   + MLA_0(N_in0(x))
    y_0   = N_post0(a_0)
    m     = MoE(y_0)                      the shortcut: NOT added here
    b_0   = a_0 + SwiGLU_0(y_0)           dense, width 12288
    a_1   = b_0 + MLA_1(N_in1(b_0))
    out   = a_1 + SwiGLU_1(N_post1(a_1)) + m

    MLA_j(y): `models/joyai.py`'s latent attention (this module REUSES its
              pieces: the low-rank query and key/value, the interleaved
              RoPE on 64 lanes, the expanded and the absorbed form, the
              cache of 512 + 64 values a token) with 64 heads and the two
              factors `mla_scale_q_lora` / `mla_scale_kv_lora`:
              c_q = RMSNorm(y W_qa) * sqrt(hidden / q_rank)       x 2
              c   = RMSNorm(c)      * sqrt(hidden / kv_rank)      x sqrt(12)
              (the rotary key is not scaled); softmax scale 1/sqrt(192)

    MoE(y):   s = softmax(y W_r) in float32 over ALL 768 outputs (512 routed
              experts, then 256 zero-compute ones)
              T = top-12 of (s + b), b the correction bias: selects, never
                  weighs
              w_e = 6 s_e for e in T              the kept scores as they are
              m = sum_{e in T, e < 512} w_e SwiGLU_e(y)        width 2048
                  + (sum_{e in T, e >= 512} w_e) y             the identity

then a final RMSNorm and an untied head. How many of a row's 12 picks are
real experts is the router's choice a token (8 in the mean under a uniform
router). `models/moe.py expert_mlp` computes `m`, zero-compute experts and
all; `LongcatConfig.held` says which of the routed experts THIS chip holds
(the published deployment spreads a layer's 512 over many chips), and a
pair on an expert held elsewhere adds nothing here.

The cache: TWO cache layers a layer (`ServeModel.sub_blocks` 2: sub-block
`j` of layer `l` writes and reads cache layer `2l + j`), each 512 lanes of
latent + the rotary key in a tile of 128, as `models/joyai.py` stores them.

Parameters: a layer's are stacked under `blk.` on a leading layer axis,
sub-block `j`'s attention, norms and dense MLP as `blk.<j>.<name>`
(`blk.0.wq_a`, `blk.1.mlp_down`), the expert path's as `blk.router`,
`blk.router_bias`, `blk.w_gate` / `blk.w_up` / `blk.w_down` `[layers,
held, ...]`. Inside every function here a SUB-BLOCK's parameters carry
joyai's names (`sub_params`: `blk.wq_a`), the first sub-block's with the
router's beside them.

Layer scopes: the sub-blocks keep `ln` / `qkv` / `attention` / `proj`; the
dense MLPs are `mlp` (holding `dense_mlp`); the expert path is a SIBLING,
`shortcut_experts` (holding models/moe.py's `router`, `moe_route`,
`experts`, `zero_experts`, and the add that ends the shortcut), so that a
profile divides into the two paths the architecture runs side by side.

Not served: the multi-token-prediction module (as in `models/joyai.py`);
the exchange of rows between the chips that share a layer's experts
(ROADMAP M6).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from . import joyai as _joyai, moe as _moe
from .common import Params, rms_norm as _rms_norm

SUB_BLOCKS = 2
SCOPE = "shortcut_experts"

# how far the correction bias spreads: the 12th and 13th largest of 768
# softmax scores of unit-variance logits lie 0.00024 apart in the mean (the
# 12th is 0.0072, the first 0.021; the twelve weigh 0.77 in all after the
# 6), so a bias of this deviation changes the top-12 SET of about 4.6% of
# the tokens a layer (5e-6: 1.2%, 1e-5: 2.4%, 5e-5: 11%, 2e-4: 38%; counted
# over 20000 rows) and the weights stay the scores'
BIAS_STD = 2e-5

# what the seeded experts' `w_down` is multiplied by, beyond the residual
# outputs' common scale: a row reaches an expert HELD here with 12 x 16/768
# = 0.25 pairs in the mean where the whole layer's 512 would give it 8, at
# weights of 0.04-0.13, so at a gain of 1 the held experts' term is a
# hundredth of a row's residual stream and no comparison of logits sees
# whether it was computed (the chip's control with the term dropped read
# 0.016, as rounding the weights to bf16 does). The absent 31 shares' part
# stands in the held experts' scale: at 32 a row's held pairs weigh what
# all of its routed pairs would if they agreed.
# benchmarks/configs/longcat_flash_chat.json has what the controls read on
# the chip at 8, 16 and 32
EXPERT_GAIN = 32.0


@dataclasses.dataclass
class LongcatConfig:
    vocab_size: int = 131072
    hidden: int = 6144
    layers: int = 28            # each of two attention sub-layers
    heads: int = 64
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_dim: int = 12288      # `ffn_hidden_size`
    expert_dim: int = 2048      # `expert_ffn_hidden_size`
    n_experts: int = 512        # `n_routed_experts`
    zero_experts: int = 256     # `zero_expert_num`, type identity
    top_k: int = 12             # `moe_topk`
    route_scale: float = 6.0    # `routed_scaling_factor`
    # (first, past the last) of the routed experts this chip holds; None:
    # all of them
    held: Optional[Tuple[int, int]] = None
    max_len: int = 131072
    rope_theta: float = 1e7
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.held is not None:
            first, past = (int(e) for e in self.held)
            if not 0 <= first < past <= self.n_experts:
                raise ValueError(
                    f"held {self.held!r} is no range of the {self.n_experts} "
                    "routed experts")
            self.held = (first, past)

    @staticmethod
    def tiny() -> "LongcatConfig":
        return LongcatConfig(vocab_size=512, hidden=64, layers=2, heads=4,
                             q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8,
                             v_dim=16, dense_dim=96, expert_dim=32,
                             n_experts=8, zero_experts=4, top_k=3,
                             held=(2, 4), max_len=128)

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    # what `models/joyai.py`'s attention asks of a configuration
    rope_inv_freq = None        # plain `theta^(-2i/d)`: no rope scaling

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_dim)

    @property
    def lora_scales(self) -> Tuple[float, float]:
        return (math.sqrt(self.hidden / self.q_rank),
                math.sqrt(self.hidden / self.kv_rank))

    @property
    def routing(self) -> _moe.Routing:
        return _moe.Routing(self.n_experts, self.top_k, score="softmax",
                            bias=True, normalise=False,
                            scale=self.route_scale,
                            zero_experts=self.zero_experts, held=self.held)

    def serve_model(self) -> "LongcatServe":
        return LongcatServe(self)


_TOP_AXES = _joyai._TOP_AXES
_EXPERT_AXES = {
    "router": ("embed", None), "router_bias": (None,),
    "w_gate": ("expert", "embed", "mlp"), "w_up": ("expert", "embed", "mlp"),
    "w_down": ("expert", "mlp", "embed"),
}
_EXPERTS = _joyai._EXPERTS


def init_layer(rng: jax.Array, cfg: LongcatConfig, l) -> Params:
    """Layer `l` of `init(rng, cfg)` alone, float32, prefix `blk.`, in
    `models/joyai.py`'s manner: normal at 1/sqrt(fan_in), norm gains 1 +
    `joyai.NORM_STD` normal, the residual outputs (`wo`, every down matrix)
    scaled by 1/sqrt(2 x the sub-blocks of the model). `wq_b` and `wkv_b`
    are drawn at 1/sqrt(rank) OVER the factor their input is scaled by
    (`lora_scales`), which is 1/sqrt(hidden): the factors exist to make a
    low-rank projection's output as large as a full-rank one's, and with
    them on top of a 1/sqrt(rank) draw the attention scores have a
    deviation of 7, the softmax is nearly one-hot and bf16 rounding of a
    score of 20 moves a token's logits by tenths (the chip's readings are
    in benchmarks/configs/longcat_flash_chat.json). The router's columns
    are 1/sqrt(hidden), so its logits have unit variance over rows of unit
    RMS: at near-zero logits 12 softmax scores of 768, times 6, weigh 0.09
    in all and the expert path hides under any tolerance. The experts'
    `w_down` carries `EXPERT_GAIN` besides, so that the few pairs a row has
    on a held expert move its logits by more than rounding. Routed expert `e`
    is drawn from a key of ITS OWN id, so a chip's share (`cfg.held`) holds
    exactly what the whole layer would hold at those ids."""
    H, D, M = cfg.hidden, cfg.dense_dim, cfg.expert_dim
    key = jax.random.fold_in(jax.random.fold_in(rng, 1), l)
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale, k=None):
        return jax.random.normal(next(keys) if k is None else k, shape,
                                 jnp.float32) * scale

    def gains(n):
        return 1.0 + normal((n,), _joyai.NORM_STD)

    a = math.sqrt(1.0 / H)
    res = 1.0 / math.sqrt(2 * SUB_BLOCKS * cfg.layers)
    q_scale, kv_scale = cfg.lora_scales
    lp = {}
    for j in range(SUB_BLOCKS):
        p = f"blk.{j}."
        lp.update({
            p + "ln_in.scale": gains(H),
            p + "wq_a": normal((H, cfg.q_rank), a),
            p + "q_norm.scale": gains(cfg.q_rank),
            p + "wq_b": normal((cfg.q_rank, cfg.heads * cfg.qk_dim),
                               math.sqrt(1.0 / cfg.q_rank) / q_scale),
            p + "wkv_a": normal((H, cfg.kv_rank + cfg.rope_dim), a),
            p + "kv_norm.scale": gains(cfg.kv_rank),
            p + "wkv_b": normal(
                (cfg.kv_rank, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
                math.sqrt(1.0 / cfg.kv_rank) / kv_scale),
            p + "wo": normal((cfg.heads * cfg.v_dim, H),
                             math.sqrt(1.0 / (cfg.heads * cfg.v_dim)) * res),
            p + "ln_post.scale": gains(H),
            p + "mlp_gate": normal((H, D), a),
            p + "mlp_up": normal((H, D), a),
            p + "mlp_down": normal((D, H), math.sqrt(1.0 / D) * res),
        })
    outputs = cfg.n_experts + cfg.zero_experts
    lp["blk.router"] = normal((H, outputs), a)
    lp["blk.router_bias"] = normal((outputs,), BIAS_STD)
    experts = next(keys)

    def expert(e):
        g, u, d = jax.random.split(jax.random.fold_in(experts, e), 3)
        return (normal((H, M), a, g), normal((H, M), a, u),
                normal((M, H), math.sqrt(1.0 / M) * res * EXPERT_GAIN, d))

    first, past = cfg.routing.held_range
    lp["blk.w_gate"], lp["blk.w_up"], lp["blk.w_down"] = jax.vmap(expert)(
        jnp.arange(first, past, dtype=jnp.int32))
    return lp


init_top = _joyai.init_top


def init(rng: jax.Array, cfg: LongcatConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """The layers stacked under `blk.` on a leading axis, made one at a
    time and cast to `dtype` as each is made (`joyai.init`'s way: the
    float32 set of a model too large for the device is never whole on
    it)."""
    def cast(lp):
        return {k: v.astype(dtype) for k, v in lp.items()}

    params = cast(init_top(rng, cfg))
    params.update(jax.lax.map(
        lambda l: cast(init_layer(rng, cfg, l)),
        jnp.arange(cfg.layers, dtype=jnp.int32)))
    axes = dict(_TOP_AXES)
    sub = {**_joyai._ATTN_AXES, **_joyai._DENSE_AXES}
    axes.update({f"blk.{j}.{k}": ("layer",) + a
                 for j in range(SUB_BLOCKS) for k, a in sub.items()})
    axes.update({"blk." + k: ("layer",) + a for k, a in _EXPERT_AXES.items()})
    return params, axes


def sub_params(lp: Params, j: int) -> Params:
    """Sub-block `j` of one layer's `lp` under joyai's names; the first
    sub-block's with the expert path's beside them (the router reads that
    sub-block's normed rows)."""
    p = f"blk.{j}."
    sub = {"blk." + k[len(p):]: v for k, v in lp.items() if k.startswith(p)}
    if j == 0:      # `blk.router`, ...: the keys with no sub-block's number
        sub.update({k: v for k, v in lp.items()
                    if not k.split(".")[1].isdigit()})
    return sub


def _dense(lp, y):
    with jax.named_scope("mlp"), jax.named_scope("dense_mlp"):
        return _moe.swiglu(y, lp["blk.mlp_gate"], lp["blk.mlp_up"],
                           lp["blk.mlp_down"])


def _mlp(lp, y, cfg: LongcatConfig, layer=None):
    """A sub-block's second half: the dense SwiGLU, and in the sub-block
    that holds the router the expert path beside it (`layer`: its index in
    the expert stacks, where `lp` holds those whole) -> (the dense MLP's
    result, or (it, the shortcut's `m`), the expert path's counters or
    None)."""
    dense = _dense(lp, y)
    if "blk.router" not in lp:
        return dense, None
    m, stats = _moe.expert_mlp(lp, y, cfg.routing, layer, scope=SCOPE)
    return (dense, m), stats


def _add_shortcut(h, m):
    with jax.named_scope(SCOPE):
        return h + m


def _sub_block(lp, x, m, positions, cfg: LongcatConfig):
    """One sub-block of the full forward pass, x `[B, T, hidden]`, `m` the
    shortcut that waits (None in the first) -> (x, m)."""
    y = _rms_norm(x, lp["blk.ln_in.scale"], cfg.rms_eps)
    q, c, kr = _joyai._qkv(lp, y, positions, cfg)
    with jax.named_scope("attention"):
        ctx = _joyai._expanded_attention(lp, q, c, kr, cfg)
    x = shard(_joyai._proj(lp, ctx, x), ("batch", "seq", "embed"))
    y = _rms_norm(x, lp["blk.ln_post.scale"], cfg.rms_eps)
    out, _ = _mlp(lp, y, cfg)
    if isinstance(out, tuple):
        return x + out[0], out[1]
    return _add_shortcut(x + out, m), None


def _block(lp, x, positions, cfg: LongcatConfig):
    m = None
    for j in range(SUB_BLOCKS):
        x, m = _sub_block(sub_params(lp, j), x, m, positions, cfg)
    return shard(x, ("batch", "seq", "embed"))


def _layer_params(params: Params) -> Params:
    return {k: v for k, v in params.items() if k.startswith("blk.")}


def apply(params: Params, cfg: LongcatConfig, ids: jax.Array) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab], attention in the expanded
    form."""
    B, T = ids.shape
    adt = jnp.dtype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = params["wte.w"][ids].astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(
            lambda h, lp: (_block(lp, h, positions, cfg), None), x,
            _layer_params(params))
    with jax.named_scope("head"):
        x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps)
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))


class LongcatServe(_joyai.JoyaiServe):
    """The layer for the serve programs (models/decoder.py): `JoyaiServe`'s
    latent cache and attention in its forms, two sub-blocks a layer, and
    between them a row carries `(h, m)`: the residual stream and the
    shortcut's result that waits for the second sub-block's end."""

    sub_blocks = SUB_BLOCKS
    prefill_counters = True     # a prompt's routing joins its step record

    def lead_params(self, params):
        return ()

    def layer_params(self, params):
        # the expert stacks stay whole: `expert_mlp` addresses them in place
        return {k: v for k, v in _layer_params(params).items()
                if k not in _EXPERTS}

    def sub_params(self, lp, j):
        return sub_params(lp, j)

    def mlp(self, lp, y, params, l):
        if "blk.router" in lp:
            lp = dict(lp, **{k: params[k] for k in _EXPERTS})
        return _mlp(lp, y, self.cfg, layer=l)

    def res_in(self, lp, h, which):
        return (h[0] if isinstance(h, tuple) else h), h

    def res_out(self, lp, kept, out, which):
        h, m = kept if isinstance(kept, tuple) else (kept, None)
        if which == "attn":
            h = self.proj(lp, out, h)
            return h if m is None else (h, m)
        if isinstance(out, tuple):      # the sub-block that holds the router
            return h + out[0], out[1]
        return _add_shortcut(h + out, m)

    def describe(self) -> Dict:
        cfg = self.cfg
        first, past = cfg.routing.held_range
        return {"sub_blocks": SUB_BLOCKS, "router_outputs":
                cfg.n_experts + cfg.zero_experts,
                "zero_experts": cfg.zero_experts,
                "held_experts": [first, past]}
