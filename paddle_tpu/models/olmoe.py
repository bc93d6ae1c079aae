"""OLMoE (Muennighoff et al. 2024, allenai/OLMoE-1B-7B): a decoder-only
transformer whose every MLP is a sparse mixture of SwiGLU experts.

One layer, as published (`OlmoeDecoderLayer` of the model's own code):

    y = RMSNorm(x; w_in)
    q = RMSNorm(y Wq; w_qn), k = RMSNorm(y Wk; w_kn), v = y Wv       no biases;
        the QK-norm spans the whole projection, before the split into heads
    q, k <- RoPE(q, k; position)      rotate-half over the whole head dimension
    h = x + softmax(q k^T / sqrt(head_dim)) v Wo                     causal
    y = RMSNorm(h; w_post)
    p = softmax(y Wg) over the experts, in float32; the top_k largest p_e kept
        and NOT renormalised
    out = h + sum_e p_e * (silu(y G_e) * (y U_e)) D_e                no capacity

then a final RMSNorm and an output head of its own. No token is ever
dropped, so a row's result depends on that row alone: the decode engine
serves it (`OlmoeConfig.serve_model()`, models/decoder.py) through the
same four programs as GPT-2. `apply` is the full forward pass for
training and scoring; both run ONE expert-layer function, `expert_mlp`
(models/moe.py, shared with the other sparse decoder).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from . import decoder as _decoder, moe as _moe
from .common import Params, rms as _rms, rms_norm as _rms_norm, \
    rope_half as _rope


@dataclasses.dataclass
class OlmoeConfig:
    vocab_size: int = 50304
    hidden: int = 2048
    layers: int = 16
    heads: int = 16
    expert_dim: int = 1024      # one expert's width (`intermediate_size`)
    n_experts: int = 64
    top_k: int = 8
    max_len: int = 4096         # positions; RoPE has no table to outgrow
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    @staticmethod
    def tiny() -> "OlmoeConfig":
        return OlmoeConfig(vocab_size=512, hidden=64, layers=2, heads=4,
                           expert_dim=32, n_experts=8, top_k=2, max_len=128)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def routing(self) -> _moe.Routing:
        return _moe.Routing(self.n_experts, self.top_k)

    def serve_model(self) -> "OlmoeServe":
        """This configuration behind the interface the decode engine
        drives (models/decoder.py)."""
        return OlmoeServe(self)


_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,),
             "head.w": ("embed", "vocab")}
_LAYER_AXES = {
    "blk.ln_in.scale": (None,), "blk.ln_post.scale": (None,),
    "blk.q_norm.scale": ("heads",), "blk.k_norm.scale": ("heads",),
    "blk.wq": ("embed", "heads"), "blk.wk": ("embed", "heads"),
    "blk.wv": ("embed", "heads"), "blk.wo": ("heads", "embed"),
    "blk.router": ("embed", None),
    "blk.w_gate": ("expert", "embed", "mlp"),
    "blk.w_up": ("expert", "embed", "mlp"),
    "blk.w_down": ("expert", "mlp", "embed"),
}


def init_layer(rng: jax.Array, cfg: OlmoeConfig, l) -> Params:
    """Layer `l` of `init(rng, cfg)` alone, in float32: every layer has a
    key of its own, so that a model whose float32 set does not fit the
    device can be made, and checked, one layer at a time (to the last
    bit but one: XLA may fold a scale differently inside `init`)."""
    H, M, E = cfg.hidden, cfg.expert_dim, cfg.n_experts
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), l), 8))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    a = math.sqrt(1.0 / H)
    down = math.sqrt(1.0 / M) / math.sqrt(2 * cfg.layers)
    return {
        "blk.ln_in.scale": jnp.ones((H,), jnp.float32),
        "blk.wq": normal((H, H), a),
        "blk.wk": normal((H, H), a),
        "blk.wv": normal((H, H), a),
        "blk.q_norm.scale": jnp.ones((H,), jnp.float32),
        "blk.k_norm.scale": jnp.ones((H,), jnp.float32),
        "blk.wo": normal((H, H), a / math.sqrt(2 * cfg.layers)),
        "blk.ln_post.scale": jnp.ones((H,), jnp.float32),
        "blk.router": normal((H, E), a),
        "blk.w_gate": normal((E, H, M), a),
        "blk.w_up": normal((E, H, M), a),
        "blk.w_down": normal((E, M, H), down),
    }


def init_top(rng: jax.Array, cfg: OlmoeConfig) -> Params:
    """The parameters of `init(rng, cfg)` outside the layers, in float32:
    embedding, final norm, head."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(rng, 0))
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32) * 0.02,
        "ln_f.scale": jnp.ones((H,), jnp.float32),
        "head.w": jax.random.normal(k_head, (H, V), jnp.float32)
        * math.sqrt(1.0 / H),
    }


def init(rng: jax.Array, cfg: OlmoeConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """Layer params are STACKED on a leading [L] axis (scan), made one
    layer at a time and cast to `dtype` as each is made: the float32 set
    of a model too large for the device is never whole on it."""
    def one(l):
        return {k: v.astype(dtype)
                for k, v in init_layer(rng, cfg, l).items()}

    params = {k: v.astype(dtype) for k, v in init_top(rng, cfg).items()}
    params.update(jax.lax.map(one, jnp.arange(cfg.layers, dtype=jnp.int32)))
    axes = dict(_TOP_AXES)
    axes.update({k: ("layer",) + a for k, a in _LAYER_AXES.items()})
    return params, axes


# Layer scopes (`jax.named_scope`: HLO metadata, no op, no run-time cost),
# named as models/gpt.py names them, with this block's own parts nested
# INSIDE them so that a reduction by the shared names still adds up: `ln`;
# `qkv` (holding `qk_norm` and `rope`); `proj`; `mlp` (models/moe.py: holding
# `router`, `moe_route`: sort, gather and weighted combine, and `experts`:
# the grouped matmuls); `head`. tests/test_layer_scopes.py holds the list.


@jax.named_scope("qkv")
def _qkv(lp, y, positions, cfg: OlmoeConfig):
    q = y @ lp["blk.wq"].astype(y.dtype)
    k = y @ lp["blk.wk"].astype(y.dtype)
    v = y @ lp["blk.wv"].astype(y.dtype)
    with jax.named_scope("qk_norm"):
        q = _rms(q, lp["blk.q_norm.scale"], cfg.rms_eps)
        k = _rms(k, lp["blk.k_norm.scale"], cfg.rms_eps)
    with jax.named_scope("rope"):
        heads = y.shape[:-1] + (cfg.heads, cfg.head_dim)
        q = _rope(q.reshape(heads), positions, cfg.rope_theta)
        k = _rope(k.reshape(heads), positions, cfg.rope_theta)
    return q.reshape(v.shape), k.reshape(v.shape), v


@jax.named_scope("proj")
def _proj(lp, ctx, res):
    return res + ctx @ lp["blk.wo"].astype(ctx.dtype)


_EXPERTS = ("blk.w_gate", "blk.w_up", "blk.w_down")


def _layer_params(params: Params) -> Params:
    return {k: v for k, v in params.items() if k.startswith("blk.")}


class OlmoeServe(_decoder.ServeModel):
    """OLMoE's block for the serve programs (models/decoder.py)."""

    def __init__(self, cfg: OlmoeConfig):
        self.cfg = cfg
        self.layers, self.heads = cfg.layers, cfg.heads
        self.head_dim = cfg.head_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len

    def layer_params(self, params):
        # the expert stacks stay whole: `expert_mlp` addresses them in place
        return {k: v for k, v in _layer_params(params).items()
                if k not in _EXPERTS}

    def embed(self, params, ids, positions):
        return params["wte.w"][ids]     # positions enter in `qkv` (RoPE)

    def norm_attn(self, lp, h):
        return _rms_norm(h, lp["blk.ln_in.scale"], self.cfg.rms_eps)

    def qkv(self, lp, y, positions):
        return _qkv(lp, y, positions, self.cfg)

    def proj(self, lp, ctx, res):
        return _proj(lp, ctx, res)

    def norm_mlp(self, lp, h):
        return _rms_norm(h, lp["blk.ln_post.scale"], self.cfg.rms_eps)

    def mlp(self, lp, y, params, l):
        return _moe.expert_mlp(dict(lp, **{k: params[k] for k in _EXPERTS}),
                               y, self.cfg.routing, layer=l)

    def head(self, params, x, prev_ids, eos_id):
        return _decoder.rms_head(params, x, prev_ids, eos_id,
                                 self.cfg.rms_eps)

    def step_facts(self, stats) -> Dict:
        return _moe.step_facts(stats)


def _block(lp, x, positions, cfg: OlmoeConfig):
    """One block of the full forward pass, x [B, T, hidden]."""
    from ..ops.pallas import attention as pa

    B, T, _ = x.shape
    y = _rms_norm(x, lp["blk.ln_in.scale"], cfg.rms_eps)
    q, k, v = _qkv(lp, y, positions, cfg)
    heads = (B, T, cfg.heads, cfg.head_dim)
    with jax.named_scope("attention"):
        ctx = pa.mha(q.reshape(heads), k.reshape(heads), v.reshape(heads),
                     causal=True, scale=1.0 / math.sqrt(cfg.head_dim))
    x = shard(_proj(lp, ctx.reshape(B, T, -1), x),
              ("batch", "seq", "embed"))
    y = _rms_norm(x, lp["blk.ln_post.scale"], cfg.rms_eps)
    out, _ = _moe.expert_mlp(lp, y, cfg.routing)
    return shard(x + out, ("batch", "seq", "embed"))


def apply(params: Params, cfg: OlmoeConfig, ids: jax.Array) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab]."""
    B, T = ids.shape
    adt = jnp.dtype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = params["wte.w"][ids].astype(adt)
    x = shard(x, ("batch", "seq", "embed"))

    def layer_body(h, lp):
        return _block(lp, h, positions, cfg), None

    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(layer_body, x, _layer_params(params))
    with jax.named_scope("head"):
        x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps)
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))


def lm_loss(params: Params, cfg: OlmoeConfig, batch: Dict[str, jax.Array],
            rng=None) -> jax.Array:
    """Next-token cross entropy; batch = {"ids": [B, T+1]}. No auxiliary
    load-balancing or router z-loss: serving is what this model is here
    for, and the loss exists so that the expert layer is differentiated."""
    ids = batch["ids"]
    logits = apply(params, cfg, ids[:, :-1]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return -ll.mean()
