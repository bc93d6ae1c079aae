"""Arcee Trinity (`model_type` `afmoe`, arcee-ai/Trinity-Mini `config.json`):
a decoder whose layers ALTERNATE between attention over a sliding window and
attention over everything, each behind a sigmoid output gate and between
two norms. With `h0 = sqrt(hidden) * E[ids]` (`mup_enabled`), layer l is

    a = RMSNorm_in(h)                                        eps 1e-5
    q = RMSNorm_q(a Wq as heads x D);  k = RMSNorm_k(a Wk as kv_heads x D)
    v = a Wv;  g = a Wg                                      no bias anywhere
    sliding layer: q, k = rotary(q, k) (rotate-half, all D lanes)
    full layer:    NO position encoding
    o = ((softmax_j q_i . k_j / sqrt(D)) v  *  sigmoid(g)) Wo
        j <= i, and in a sliding layer i - j < window (the query's own key
        among the `window`); `heads / kv_heads` query heads read one K/V head
    h = h + RMSNorm_post_attn(o)
    m = RMSNorm_pre_mlp(h)
    f = dense MLP (the first `dense_layers` layers) | shared(m) + experts(m)
    h = h + RMSNorm_post_mlp(f)

and the logits are `RMSNorm(h) W_head`, untied. The layer's kind follows
`layer_types`, here `pattern`, a character a LAYER: `W` a sliding-window
layer, `*` a full one (three to one, published).

Experts (`models/moe.py expert_mlp`): the router's float32 sigmoid scores
over all `n_experts`; the `top_k` largest of score + `expert_bias` are
picked, weighed by the score alone, divided by their sum and scaled by
`route_scale`: `Routing(score="sigmoid", bias=True, normalise=True,
scale=route_scale, shared=True)` term for term (tests/test_afmoe.py holds the
published rule and `moe.route` equal). `held` says which of the routed
experts THIS chip holds.

How a layer is expressed to the serve programs: as TWO blocks of
`decoder.mixer_layers`' pattern, `WE` or `*E` (as `models/granite_hybrid.py`
expresses its `ME` / `*E`), with the block kind `W` this model brought: a
`W` block's K/V live in the WINDOW kind's pools, a ring of blocks a sequence
(`serving/kv_cache.py`), a `*` block's in the global kind's. A block there
is `res_out(kept, mixer(norm(res_in(h))))`: the pre-norm is the block's
`norm`, the post-norm sits in `res_out`, the gate's rows ride with `q` from
`qkv` to the attention forms, which multiply the context by `sigmoid(g)`
before `res_out` projects it. 16 blocks unrolled; a scan over periods would
save lowering time this size does not need (28 blocks lower in 7 s: PERF.md,
PR 52). The parameters are one stack a KIND: `wattn.` (the `W` layers'
attention), `attn.` (the `*` layers'), `dense.` (the leading layers' MLPs),
`moe.` (the expert layers), each with the `norm.scale` that precedes it and
the `post.scale` that follows.

A prompt is walked in slices of `prompt_slice` tokens
(`decoder.prefill_sliced`, `decoder.gqa_slice`): a window kind needs it (a
ring holds the window and one slice), and a 32k prompt's scores would not
fit otherwise.

Seeded weights. Every matrix is drawn at 1/sqrt(fan_in); the residual
outputs (`wo`, every down matrix) at a quarter of that, so that a post-norm
which is dropped leaves a branch a quarter as heavy and the logits say so.
QK-norm fixes a head's length whatever `Wq` is, so the scores' deviation
(`ATTN_SCORE_STD`, and why it is not Nemotron's) is carried by the q and k
gains. The routed
experts' `w_down` carries `EXPERT_GAIN`; expert `e` is drawn from a key of
its own id, so a chip's share holds what the whole layer would at those ids.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from . import decoder as _decoder, moe as _moe
from .common import Params, rms as _rms, rms_norm as _rms_norm, \
    rope_half as _rope
from .joyai import BIAS_STD, NORM_STD

# a block kind's stack prefix; an `E` block is `dense.` or `moe.` by its layer
KINDS = {"W": "wattn", "*": "attn"}

# Deviation of a seeded attention score q.k / sqrt(head_dim), which the q and
# k gains carry (QK-norm fixes a head's length whatever `Wq` is): 1, the
# plain draw. EVERY layer of this model attends, and a peaked softmax
# multiplies a relative error of its inputs by about 1.4 times this
# deviation: at `nemotron_h.ATTN_SCORE_STD` 5 (one layer in ten attends
# there) eight attention layers in a row turn bf16 rounding into another
# model. Chip readings of PR 60, 8 layers at the published widths, 4
# sequences of 0.7k-4k tokens, the bf16 engine's tokens in the float32
# reference's logits (`afmoe_ref.verdict`; tokens equal to its argmax of
# 128): 11.2 (14) at 5, 0.18 (96) at 2, 0.012 (111) at 1, where the
# reference with rotary positions on the full layers too reads 26 / 3.6 /
# 0.47 and with the window ignored 29 / 12.6 / 9.4: the faults stay 40 and
# 800 times the program's own rounding at 1, twice and thrice at 5
ATTN_SCORE_STD = 1.0

# what the seeded routed experts' `w_down` is multiplied by: eight sigmoid
# scores normalised to 2.826 weigh 0.35 each, half of them on an expert held
# here, so independent experts' held term is 0.7 of ONE expert's row beside a
# shared expert of a whole row; at 2 it weighs more than the shared expert
# and leaving it out (or adding the absent half) moves the logits by more
# than bf16 rounding does
EXPERT_GAIN = 2.0
# the residual outputs' draw, beside 1/sqrt(fan_in): a post-norm makes every
# branch a unit row whatever this is, so it shows only where the norm is gone
RESIDUAL_DRAW = 0.25


def blocks(pattern: str) -> str:
    """The layers' pattern as `decoder.mixer_layers` runs it: every layer
    its attention's block, then its MLP's (`WW*` -> `WEWE*E`)."""
    return "".join(kind + "E" for kind in pattern)


@dataclasses.dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden: int = 2048
    pattern: str = "WWW*" * 8           # `layer_types`: a LAYER a character
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    window: int = 2048                  # `sliding_window`
    rope_theta: float = 10000.0
    dense_layers: int = 2               # `num_dense_layers`
    dense_dim: int = 6144               # `intermediate_size`
    expert_dim: int = 1024              # `moe_intermediate_size`
    n_experts: int = 128                # `num_experts`: the router's width
    top_k: int = 8
    route_scale: float = 2.826
    # (first, past the last) of the routed experts this chip holds; None:
    # all of them
    held: Optional[Tuple[int, int]] = None
    mup_enabled: bool = True            # the embedding times sqrt(hidden)
    prompt_slice: int = 1024            # tokens a slice of the prefill's walk
    max_len: int = 131072
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.pattern) - {"W", "*"}:
            raise ValueError(f"a layer is `W` or `*`: {self.pattern!r}")
        if self.held is not None:
            first, past = (int(e) for e in self.held)
            if not 0 <= first < past <= self.n_experts:
                raise ValueError(
                    f"held {self.held!r} is no range of the {self.n_experts} "
                    "routed experts")
            self.held = (first, past)

    @staticmethod
    def tiny() -> "AfmoeConfig":
        return AfmoeConfig(
            vocab_size=512, hidden=64, pattern="WW*W", heads=4, kv_heads=2,
            head_dim=16, window=32, dense_layers=1, dense_dim=96,
            expert_dim=24, n_experts=8, top_k=3, held=(0, 4),
            prompt_slice=16, max_len=256)

    @property
    def layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return blocks(self.pattern).count(kind)

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.hidden) if self.mup_enabled else 1.0

    @property
    def routing(self) -> _moe.Routing:
        return _moe.Routing(self.n_experts, self.top_k, score="sigmoid",
                            bias=True, normalise=True,
                            scale=self.route_scale, shared=True,
                            form="swiglu", held=self.held)

    def serve_model(self) -> "AfmoeServe":
        return AfmoeServe(self)


_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,),
             "head.w": ("embed", "vocab")}
_ATTN_AXES = {"norm.scale": (None,), "wq": ("embed", "heads"),
              "wk": ("embed", "heads"), "wv": ("embed", "heads"),
              "wg": ("embed", "heads"), "q_norm.scale": (None,),
              "k_norm.scale": (None,), "wo": ("heads", "embed"),
              "post.scale": (None,)}
_STACK_AXES = {
    "wattn": _ATTN_AXES, "attn": _ATTN_AXES,
    "dense": {"norm.scale": (None,), "mlp_gate": ("embed", "mlp"),
              "mlp_up": ("embed", "mlp"), "mlp_down": ("mlp", "embed"),
              "post.scale": (None,)},
    "moe": {"norm.scale": (None,), "router": ("embed", None),
            "router_bias": (None,),
            "w_gate": ("expert", "embed", "mlp"),
            "w_up": ("expert", "embed", "mlp"),
            "w_down": ("expert", "mlp", "embed"),
            "shared_gate": ("embed", "mlp"), "shared_up": ("embed", "mlp"),
            "shared_down": ("mlp", "embed"), "post.scale": (None,)},
}
_EXPERTS = ("w_gate", "w_up", "w_down")


def stack_of(cfg: AfmoeConfig, b: int) -> str:
    """The stack (`wattn`, `attn`, `dense`, `moe`) that holds block `b` of
    `blocks(cfg.pattern)`."""
    kind = blocks(cfg.pattern)[b]
    if kind != "E":
        return KINDS[kind]
    return "dense" if b // 2 < cfg.dense_layers else "moe"


def init_layer(rng: jax.Array, cfg: AfmoeConfig, b, stack=None) -> Params:
    """Block `b` of `blocks(cfg.pattern)` (layer `b // 2`'s attention, or
    its MLP) of `init(rng, cfg)` alone, float32, prefix `blk.`: every block
    has a key of its own, so a model whose float32 set does not fit the
    device can be made, and checked, a block at a time. `stack` is the
    block's (`stack_of`); left out, that of a Python int `b`. Routed expert
    `e` is drawn from a key of ITS OWN id, so a chip's share (`cfg.held`)
    holds exactly what the whole layer would hold at those ids."""
    stack = stack or stack_of(cfg, b)
    H = cfg.hidden
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), b), 12))

    def normal(shape, scale, k=None):
        return jax.random.normal(next(keys) if k is None else k, shape,
                                 jnp.float32) * scale

    def gains(n, about=1.0):
        return about * (1.0 + normal((n,), NORM_STD))

    a = math.sqrt(1.0 / H)
    lp = {"blk.norm.scale": gains(H), "blk.post.scale": gains(H)}
    if stack in ("wattn", "attn"):
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        # QK-norm makes every head a row of length sqrt(D) x its gain: the
        # scores' deviation is the gains' to carry
        peak = math.sqrt(ATTN_SCORE_STD)
        lp.update({
            "blk.wq": normal((H, q), a), "blk.wk": normal((H, kv), a),
            "blk.wv": normal((H, kv), a), "blk.wg": normal((H, q), a),
            "blk.q_norm.scale": gains(cfg.head_dim, peak),
            "blk.k_norm.scale": gains(cfg.head_dim, peak),
            "blk.wo": normal((q, H), math.sqrt(1.0 / q) * RESIDUAL_DRAW),
        })
    elif stack == "dense":
        D = cfg.dense_dim
        lp.update({
            "blk.mlp_gate": normal((H, D), a), "blk.mlp_up": normal((H, D), a),
            "blk.mlp_down": normal((D, H),
                                   math.sqrt(1.0 / D) * RESIDUAL_DRAW),
        })
    else:
        M = cfg.expert_dim
        down = math.sqrt(1.0 / M) * RESIDUAL_DRAW
        lp.update({
            "blk.router": normal((H, cfg.n_experts), a),
            "blk.router_bias": normal((cfg.n_experts,), BIAS_STD),
            "blk.shared_gate": normal((H, M), a),
            "blk.shared_up": normal((H, M), a),
            "blk.shared_down": normal((M, H), down),
        })
        experts = next(keys)

        def expert(e):
            g, u, d = jax.random.split(jax.random.fold_in(experts, e), 3)
            return (normal((H, M), a, g), normal((H, M), a, u),
                    normal((M, H), down * EXPERT_GAIN, d))

        first, past = cfg.routing.held_range
        lp["blk.w_gate"], lp["blk.w_up"], lp["blk.w_down"] = jax.vmap(
            expert)(jnp.arange(first, past, dtype=jnp.int32))
    return lp


def init_top(rng: jax.Array, cfg: AfmoeConfig) -> Params:
    """The parameters outside the layers, float32: embedding, final norm,
    the untied head. The embedding is drawn at 0.02, so `sqrt(hidden)` times
    it is a row of RMS 0.9 beside branches of RMS 1 each (the post-norms)."""
    k_emb, k_head, k_norm = jax.random.split(jax.random.fold_in(rng, 0), 3)
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32) * 0.02,
        "ln_f.scale": 1.0 + NORM_STD * jax.random.normal(
            k_norm, (H,), jnp.float32),
        "head.w": jax.random.normal(k_head, (H, V), jnp.float32)
        * math.sqrt(1.0 / H),
    }


def stack_blocks(cfg: AfmoeConfig, stack: str):
    """The positions in `blocks(cfg.pattern)` of the blocks of `stack`."""
    return [b for b in range(2 * cfg.layers) if stack_of(cfg, b) == stack]


def init(rng: jax.Array, cfg: AfmoeConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """The blocks of a stack under the stack's prefix, made one block and
    ONE TENSOR at a time and cast to `dtype` as each is made."""
    params = {k: v.astype(dtype) for k, v in init_top(rng, cfg).items()}
    axes = dict(_TOP_AXES)
    for stack, names in _STACK_AXES.items():
        where = jnp.asarray(stack_blocks(cfg, stack), jnp.int32)
        if not where.size:
            continue
        for name, ax in names.items():
            params[f"{stack}.{name}"] = jax.lax.map(
                lambda b: init_layer(rng, cfg, b, stack)["blk." + name]
                .astype(dtype), where)
            axes[f"{stack}.{name}"] = ("layer",) + ax
    return params, axes


def block_params(params: Params, cfg: AfmoeConfig, kind: str, i: int,
                 skip=()) -> Params:
    """Block `i` AMONG THE BLOCKS OF ITS KIND out of the flat set, under
    `blk.`: an `E` block's from `dense.` while `i` is a leading layer, from
    `moe.` (at `i - dense_layers`) after; without the tensors in `skip`."""
    if kind == "E":
        stack, i = ("dense", i) if i < cfg.dense_layers \
            else ("moe", i - cfg.dense_layers)
    else:
        stack = KINDS[kind]
    prefix = stack + "."
    return {"blk." + k[len(prefix):]: v[i] for k, v in params.items()
            if k.startswith(prefix) and k[len(prefix):] not in skip}


# Layer scopes: `ln` (the four norms); `qkv` (holding `qk_norm` and, in a
# sliding layer, `rope`); `attention` (a full layer's) or `window_attention`
# (a sliding layer's: the serve programs name it, decoder.py); `proj`; `mlp`
# (models/moe.py's `router`, `moe_route`, `experts`, `shared_expert`, or the
# dense MLP); `head`. tests/test_afmoe.py holds the list.


@jax.named_scope("qkv")
def _qkv(lp, y, positions, cfg: AfmoeConfig, windowed: bool):
    """(q with the gate's rows behind it `[..., 2 * heads * D]`, k, v): the
    per-head norms BEFORE the rotation, which a sliding layer alone has."""
    lead = y.shape[:-1]
    q = (y @ lp["blk.wq"].astype(y.dtype)).reshape(
        lead + (cfg.heads, cfg.head_dim))
    k = (y @ lp["blk.wk"].astype(y.dtype)).reshape(
        lead + (cfg.kv_heads, cfg.head_dim))
    v = y @ lp["blk.wv"].astype(y.dtype)
    g = y @ lp["blk.wg"].astype(y.dtype)
    with jax.named_scope("qk_norm"):
        q = _rms(q, lp["blk.q_norm.scale"], cfg.rms_eps)
        k = _rms(k, lp["blk.k_norm.scale"], cfg.rms_eps)
    if windowed:
        with jax.named_scope("rope"):
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    return (jnp.concatenate([q.reshape(lead + (-1,)), g], axis=-1),
            k.reshape(v.shape), v)


def _gated(qg, attend):
    """`attend(q) * sigmoid(g)` for `qg` = q with the gate's rows behind it
    (`_qkv`); the sigmoid in float32."""
    q, g = jnp.split(qg, 2, axis=-1)
    ctx = attend(q)
    return (ctx.astype(jnp.float32)
            * jax.nn.sigmoid(g.astype(jnp.float32))).astype(ctx.dtype)


def _res_out(lp, kept, out, which, cfg: AfmoeConfig):
    """`kept + RMSNorm_post(branch)`: an attention block's branch is the
    gated context through `wo`."""
    if which == "attn":
        with jax.named_scope("proj"):
            out = out @ lp["blk.wo"].astype(out.dtype)
    return kept + _rms_norm(out, lp["blk.post.scale"], cfg.rms_eps)


def _no_counts(cfg: AfmoeConfig):
    """A dense block's counters: an expert block's keys, all zero, so that
    the blocks' counters stack."""
    zero = jnp.int32(0)
    return {"experts_hit": zero, "expert_load_max": zero,
            **({"held_pairs": zero, "zero_pairs": zero, "pairs": zero}
               if cfg.routing.partial else {})}


def _mlp(lp, y, cfg: AfmoeConfig, layer=None):
    if "blk.mlp_gate" in lp:
        with jax.named_scope("mlp"):
            return _moe.swiglu(y, lp["blk.mlp_gate"], lp["blk.mlp_up"],
                               lp["blk.mlp_down"]), _no_counts(cfg)
    return _moe.expert_mlp(lp, y, cfg.routing, layer=layer)


def _embed(params, ids, cfg: AfmoeConfig):
    """`sqrt(hidden) * E[ids]`, float32: the caller rounds once."""
    return params["wte.w"][ids].astype(jnp.float32) * cfg.embed_scale


class AfmoeServe(_decoder.ServeModel):
    """The layers for the serve programs (models/decoder.py): the pattern
    `WE` / `*E` a layer, TWO kinds of cache (`window`), a prompt walked in
    slices, the post-norms in `res_out` and the output gate in the
    attention forms."""

    def __init__(self, cfg: AfmoeConfig):
        self.cfg = cfg
        self.pattern = blocks(cfg.pattern)
        self.window = cfg.window
        self.layers, self.heads = cfg.layers, cfg.heads
        self.head_dim = cfg.head_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len
        self.prompt_slice = cfg.prompt_slice

    @property
    def kv_heads(self) -> int:
        return self.cfg.kv_heads

    @property
    def kv_layers(self) -> int:
        return self.cfg.count("*")      # the global kind's; `W`: window_layers

    def block_params(self, params, kind, i):
        # the expert stacks stay whole: `expert_mlp` addresses them in place
        return block_params(params, self.cfg, kind, i, skip=_EXPERTS)

    def embed(self, params, ids, positions):
        return _embed(params, ids, self.cfg)    # positions enter in `qkv`

    def norm(self, lp, h):
        return _rms_norm(h, lp["blk.norm.scale"], self.cfg.rms_eps)

    def qkv(self, lp, y, positions, windowed=False):
        return _qkv(lp, y, positions, self.cfg, windowed)

    def mlp(self, lp, y, params, l):
        if "blk.mlp_gate" not in lp:
            lp = dict(lp, **{"blk." + k: params["moe." + k]
                             for k in _EXPERTS})
            l = l - self.cfg.dense_layers       # its index in `moe.`
        return _mlp(lp, y, self.cfg, layer=l)

    def res_out(self, lp, kept, out, which):
        return _res_out(lp, kept, out, which, self.cfg)

    # the attention forms: `q` carries the gate's rows (`_gated`); a `W`
    # block's are told the window (no `attend_prompt`: every prompt is
    # walked in slices)

    def attend_cached(self, lp, q, keys, vals, pos, extra=(), window=None):
        return _gated(q, lambda q: _decoder.mha_cached(
            q, keys, vals, pos, self.heads, self.kv_heads, window=window))

    def attend_paged(self, lp, q, k_pool, v_pool, layer, block_tables,
                     positions, rated=(), window=None):
        from ..ops.pallas import paged_attention as pa

        return _gated(q, lambda q: pa.paged_gqa_attention(
            q, k_pool, v_pool, layer, block_tables, positions,
            heads=self.heads, kv_heads=self.kv_heads, window=window))

    def attend_slice(self, lp, q, k_pool, v_pool, rated, layer, block_table,
                     start, block_size, window=None):
        return _gated(q, lambda q: _decoder.gqa_slice(
            q, k_pool, v_pool, layer, block_table, start, block_size,
            self.heads, self.kv_heads, window=window)), rated

    def head(self, params, x, prev_ids, eos_id):
        return _decoder.rms_head(params, x, prev_ids, eos_id,
                                 self.cfg.rms_eps)

    def step_facts(self, stats) -> Dict:
        return _moe.step_facts(stats)

    def describe(self) -> Dict:
        cfg = self.cfg
        first, past = cfg.routing.held_range
        return {"layer": "attention then an MLP, between two norms each",
                "blocks": self.pattern, "window": cfg.window,
                "window_layers": self.window_layers,
                "global_layers": self.kv_layers,
                "router_outputs": cfg.n_experts,
                "held_experts": [first, past]}


def _block(kind, lp, x, positions, cfg: AfmoeConfig):
    """One block of the full forward pass, x [B, T, hidden]."""
    y = _rms_norm(x, lp["blk.norm.scale"], cfg.rms_eps)
    if kind == "E":
        out, _ = _mlp(lp, y, cfg)
        x = _res_out(lp, x, out, "mlp", cfg)
    else:
        windowed = kind == "W"
        q, k, v = _qkv(lp, y, positions, cfg, windowed)
        with jax.named_scope("window_attention" if windowed
                             else "attention"):
            ctx = _gated(q, lambda q: _decoder.gqa_prompt(
                q, k, v, cfg.heads, cfg.kv_heads,
                window=cfg.window if windowed else None))
        x = _res_out(lp, x, ctx, "attn", cfg)
    return shard(x, ("batch", "seq", "embed"))


def apply(params: Params, cfg: AfmoeConfig, ids: jax.Array) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab]."""
    B, T = ids.shape
    adt = jnp.dtype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = _embed(params, ids, cfg).astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    with jax.named_scope("layers"):
        for kind, i in _decoder.pattern_blocks(blocks(cfg.pattern)):
            x = _block(kind, block_params(params, cfg, kind, i), x,
                       positions, cfg)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps)
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))
