"""IBM Granite 4.0-H (`model_type` `granitemoehybrid`,
ibm-granite/granite-4.0-h-small `config.json`): a decoder whose every LAYER
is a mixer followed by sparse experts, under four fixed multipliers. With
`h0 = embedding_multiplier * E[ids]`, layer l is

    h = h + residual_multiplier * mixer_l(RMSNorm(h))           eps 1e-5
    y = RMSNorm(h)
    h = h + residual_multiplier * (experts(y) + shared(y))

and the logits are `(RMSNorm(h) E^T) / logits_scaling`: the head is the
embedding. The mixer's kind follows `layer_types`, here `pattern`, a
character a layer: `M` a Mamba-2 mixer, `*` attention.

`M` is `models/nemotron_h.py`'s Mamba-2 mixer, imported and not copied
(`mamba_prompt`, `mamba_token`, `_token_inputs`, `_ssm_out`: both models'
tests and cells guard it), at Granite's shape: 128 heads of 64 on ONE B and
C (`ssm_groups` 1), a state of 128 lanes, ONE gated-norm group over all of
`inner` = 8192, chunks of 256 in a prompt's scan.

`*`: `heads` query heads over `kv_heads` K/V heads, causal softmax at
`attention_multiplier` (1/128 where 1/sqrt(head_dim) is 1/11.3), NO
position encoding, no bias.

Experts (`models/moe.py expert_mlp`): the router's float32 logits over all
`n_experts`; the published rule keeps the `top_k` largest LOGITS and takes
a softmax over the kept, which in exact arithmetic is `Routing(score=
"softmax", normalise=True)`: softmax is monotone, and the kept scores
divided by their sum are the softmax of the kept logits
(tests/test_granite_hybrid.py holds the two equal). An expert is `down(
silu(gate y) * up y)` (published fused as `input_linear`, gate's columns
then up's), the shared expert the same at `shared_dim`, added unweighted.
`held` says which of the routed experts THIS chip holds
(`moe.Routing.held`: a pair on an absent expert adds nothing here).

How a layer is expressed to the serve programs: as TWO blocks of
`decoder.mixer_layers`' pattern, `ME` or `*E` (`GraniteHybridServe.pattern`
is `blocks(cfg.pattern)`), not as a layer kind of its own. A block there is
already `res_out(kept, mixer(norm(res_in(h))))` with the kind's parameters
in a stack of their own, which is this layer's half exactly; the
multipliers are the model's `res_out` and `embed`, and the layer loop, the
programs and the engine are untouched. The parameters are therefore one
stack a KIND: `mamba.` (the `M` layers' mixers), `attn.`, `moe.` (every
layer's experts, `[layers, held, ...]`), each with the `norm.scale` that
precedes it.

A prompt is walked in slices of `prompt_slice` tokens
(`decoder.prefill_sliced`): a chunk's in-chunk matrix is `[heads, 256,
256]` float32, 33.5 MB with two more of its size beside it, so a whole
4096-token prompt would hold 16 x 100 MB a layer. A slice's Mamba mixer
starts from the row the slice before left (`ssm_slice`: the state AND the
convolution's last 3 inputs), its attention reads the cache so far
(`decoder.gqa_slice`).

Seeded weights. Every matrix is drawn at 1/sqrt(fan_in) and NO residual
output carries a depth factor of the init: the published
`residual_multiplier` 0.22 is that factor (1/sqrt(20.7), and a layer has
two residual outputs), so a branch adds 0.22 of a unit row and dropping the
multiplier, or the embedding's 12 beside it, moves every logit (`init_top`
says how the tied embedding is drawn, and why not at 0.02). The routed
experts' `w_down` carries `EXPERT_GAIN`, the attention's `wo` `ATTN_GAIN`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from . import decoder as _decoder, moe as _moe, nemotron_h as _nh
from .common import Params, rms_norm as _rms_norm
from .joyai import NORM_STD
from .nemotron_h import ATTN_SCORE_STD

KINDS = _nh.KINDS       # a kind's stack prefix: `mamba.`, `moe.`, `attn.`

# what the seeded routed experts' `w_down` is multiplied by: ten softmax
# weights of unit-variance logits have a sum of squares near 0.12, so ten
# independent experts' weighted sum is a third of ONE expert's row, and the
# half of it held here a quarter, beside a shared expert and a mixer of a
# whole row each: at 4 the held experts' term weighs what the shared
# expert does, and leaving it out (or adding the absent half) moves the
# logits by more than bf16 rounding does
EXPERT_GAIN = 4.0

# and the attention's `wo`: ONE layer in ten attends, a twentieth of the
# residual outputs, and at a plain draw what it attends TO (positions it
# must not have, the softmax's scale) moves the logits by little more than
# bf16 rounding does (chip readings of PR 58 at a gain of 1: rotary
# positions applied 0.025 where the program's own rounding reads 0.005-0.01)
ATTN_GAIN = 2.0


def blocks(pattern: str) -> str:
    """The layers' pattern as `decoder.mixer_layers` runs it: every layer
    its mixer's block, then its experts' (`MM*` -> `MEME*E`)."""
    return "".join(kind + "E" for kind in pattern)


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden: int = 4096
    pattern: str = "MMMMM*MMMM" * 4     # `layer_types`: a LAYER a character
    # M (`mamba_*`)
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # experts
    expert_dim: int = 768       # `intermediate_size`: ONE expert's width
    shared_dim: int = 1536      # `shared_intermediate_size`
    n_experts: int = 72         # `num_local_experts`: the router's width
    top_k: int = 10
    # (first, past the last) of the routed experts this chip holds; None:
    # all of them
    held: Optional[Tuple[int, int]] = None
    # *
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 128
    # the four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    prompt_slice: int = 1024    # tokens a slice of the prefill's walk
    max_len: int = 131072
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.pattern) - {"M", "*"}:
            raise ValueError(f"a layer is `M` or `*`: {self.pattern!r}")
        if self.held is not None:
            first, past = (int(e) for e in self.held)
            if not 0 <= first < past <= self.n_experts:
                raise ValueError(
                    f"held {self.held!r} is no range of the {self.n_experts} "
                    "routed experts")
            self.held = (first, past)

    @staticmethod
    def tiny() -> "GraniteHybridConfig":
        return GraniteHybridConfig(
            vocab_size=512, hidden=64, pattern="MM*M", ssm_heads=8,
            ssm_head_dim=8, ssm_groups=1, ssm_state=16, chunk=8,
            expert_dim=24, shared_dim=48, n_experts=8, top_k=3, held=(0, 4),
            heads=4, kv_heads=2, head_dim=16, attention_multiplier=1.0 / 16,
            prompt_slice=8, max_len=128)

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    def count(self, kind: str) -> int:
        """Blocks of `kind` (`M`, `*`, or `E`: one a layer)."""
        return blocks(self.pattern).count(kind)

    @property
    def routing(self) -> _moe.Routing:
        return _moe.Routing(self.n_experts, self.top_k, score="softmax",
                            normalise=True, shared=True, form="swiglu",
                            held=self.held)

    def serve_model(self) -> "GraniteHybridServe":
        return GraniteHybridServe(self)


_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,)}
_KIND_AXES = {
    "M": _nh._KIND_AXES["M"],
    "E": {"norm.scale": (None,), "router": ("embed", None),
          "w_gate": ("expert", "embed", "mlp"),
          "w_up": ("expert", "embed", "mlp"),
          "w_down": ("expert", "mlp", "embed"),
          "shared_gate": ("embed", "mlp"), "shared_up": ("embed", "mlp"),
          "shared_down": ("mlp", "embed")},
    "*": _nh._KIND_AXES["*"],
}
_EXPERTS = ("w_gate", "w_up", "w_down")


def init_layer(rng: jax.Array, cfg: GraniteHybridConfig, b, kind=None
               ) -> Params:
    """Block `b` of `blocks(cfg.pattern)` (layer `b // 2`'s mixer, or its
    experts) of `init(rng, cfg)` alone, float32, prefix `blk.`: every block
    has a key of its own, so a model whose float32 set does not fit the
    device can be made, and checked, a block at a time. `kind` is the
    block's character; left out, that of a Python int `b`. Routed expert
    `e` is drawn from a key of ITS OWN id, so a chip's share (`cfg.held`)
    holds exactly what the whole layer would hold at those ids."""
    kind = kind or blocks(cfg.pattern)[b]
    H = cfg.hidden
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), b), 12))

    def normal(shape, scale, k=None):
        return jax.random.normal(next(keys) if k is None else k, shape,
                                 jnp.float32) * scale

    def gains(n):
        return 1.0 + normal((n,), NORM_STD)

    a = math.sqrt(1.0 / H)
    lp = {"blk.norm.scale": gains(H)}
    if kind == "M":
        nh, inner = cfg.ssm_heads, cfg.inner
        # Mamba-2's own, as models/nemotron_h.py draws them
        dt = jnp.exp(jax.random.uniform(next(keys), (nh,), jnp.float32)
                     * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                     + math.log(cfg.dt_min))
        dt = jnp.maximum(dt, cfg.dt_floor)
        lp.update({
            "blk.in_proj": normal((H, inner + cfg.conv_dim + nh), a),
            "blk.conv_w": normal((cfg.conv_kernel, cfg.conv_dim),
                                 math.sqrt(1.0 / cfg.conv_kernel)),
            "blk.conv_b": normal((cfg.conv_dim,), 0.1),
            "blk.dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "blk.A_log": jnp.log(jax.random.uniform(
                next(keys), (nh,), jnp.float32, 1.0, 16.0)),
            "blk.D": jnp.ones((nh,), jnp.float32),
            "blk.gnorm.scale": gains(inner),
            "blk.out_proj": normal((inner, H), math.sqrt(1.0 / inner)),
        })
    elif kind == "E":
        M, Ms = cfg.expert_dim, cfg.shared_dim
        lp.update({
            "blk.router": normal((H, cfg.n_experts), a),
            "blk.shared_gate": normal((H, Ms), a),
            "blk.shared_up": normal((H, Ms), a),
            "blk.shared_down": normal((Ms, H), math.sqrt(1.0 / Ms)),
        })
        experts = next(keys)

        def expert(e):
            g, u, d = jax.random.split(jax.random.fold_in(experts, e), 3)
            return (normal((H, M), a, g), normal((H, M), a, u),
                    normal((M, H), math.sqrt(1.0 / M) * EXPERT_GAIN, d))

        first, past = cfg.routing.held_range
        lp["blk.w_gate"], lp["blk.w_up"], lp["blk.w_down"] = jax.vmap(
            expert)(jnp.arange(first, past, dtype=jnp.int32))
    elif kind == "*":
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        # q and k drawn so that a score's deviation is `ATTN_SCORE_STD`
        # UNDER `attention_multiplier`: at 1/128 the plain draw's scores
        # deviate by 0.09 and the softmax is a plain mean
        peak = math.sqrt(ATTN_SCORE_STD / (cfg.attention_multiplier
                                           * math.sqrt(cfg.head_dim)))
        lp.update({
            "blk.wq": normal((H, q), a * peak),
            "blk.wk": normal((H, kv), a * peak),
            "blk.wv": normal((H, kv), a),
            "blk.wo": normal((q, H), math.sqrt(1.0 / q) * ATTN_GAIN),
        })
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return lp


def init_top(rng: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """The parameters outside the layers, float32: the embedding, which is
    the head too, and the final norm. The embedding is drawn at 1 /
    (`embedding_multiplier` x sqrt(hidden)), so that h0 has unit LENGTH (an
    RMS of 1/64 beside branches of 0.22 each). The head is TIED: a token's
    own embedding scores `12 |E|^2 sqrt(hidden) / rms(h)` deviations of the
    other tokens' logits above them, and at the families' usual 0.02 that
    is 15 deviations after ten layers: every pick would be the token that
    was fed, whatever the layers computed (the first CPU reading of the
    cell's comparison: every fault read 0.0). At this draw it is about 1."""
    k_emb, k_norm = jax.random.split(jax.random.fold_in(rng, 0), 2)
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32)
        / (cfg.embedding_multiplier * math.sqrt(H)),
        "ln_f.scale": 1.0 + NORM_STD * jax.random.normal(
            k_norm, (H,), jnp.float32),
    }


def kind_blocks(cfg: GraniteHybridConfig, kind: str):
    """The positions in `blocks(cfg.pattern)` of the blocks of `kind`."""
    return [b for b, c in enumerate(blocks(cfg.pattern)) if c == kind]


def init(rng: jax.Array, cfg: GraniteHybridConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """The blocks of a kind stacked under the kind's prefix, made one block
    and ONE TENSOR at a time and cast to `dtype` as each is made
    (`nemotron_h.init`'s way)."""
    params = {k: v.astype(dtype) for k, v in init_top(rng, cfg).items()}
    axes = dict(_TOP_AXES)
    for kind, prefix in KINDS.items():
        where = jnp.asarray(kind_blocks(cfg, kind), jnp.int32)
        if not where.size:
            continue
        for name, ax in _KIND_AXES[kind].items():
            params[f"{prefix}.{name}"] = jax.lax.map(
                lambda b: init_layer(rng, cfg, b, kind)["blk." + name]
                .astype(dtype), where)
            axes[f"{prefix}.{name}"] = ("layer",) + ax
    return params, axes


block_params = _nh.block_params     # the same stacks under the same prefixes


# Layer scopes: `ln`; then by the block's kind `ssm` (nemotron_h's `ssm_in`,
# `conv`, `scan`, `ssm_out`), `mlp` (models/moe.py's `router`, `moe_route`,
# `experts`, `shared_expert`), or `qkv`, `attention`, `proj`; `head`.


def _add(h, out, cfg: GraniteHybridConfig):
    """`h + residual_multiplier * out`, the product and the sum in float32
    and rounded once."""
    f32 = jnp.float32
    return (h.astype(f32) + cfg.residual_multiplier * out.astype(f32)) \
        .astype(h.dtype)


@jax.named_scope("proj")
def _proj(lp, ctx, res, cfg: GraniteHybridConfig):
    return _add(res, jnp.dot(ctx, lp["blk.wo"].astype(ctx.dtype),
                             preferred_element_type=jnp.float32), cfg)


def _embed(params, ids, cfg: GraniteHybridConfig):
    """`embedding_multiplier * E[ids]`, float32: the caller rounds once."""
    return params["wte.w"][ids].astype(jnp.float32) \
        * cfg.embedding_multiplier


def _logits(params, x, cfg: GraniteHybridConfig):
    """Final norm and the TIED head, contracted over the embedding's lanes
    (no transposed copy), float32 logits over `logits_scaling`."""
    x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps)
    return jax.lax.dot_general(
        x, params["wte.w"].astype(x.dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (1.0 / cfg.logits_scaling)


class GraniteHybridServe(_nh.NemotronHServe):
    """The layers for the serve programs (models/decoder.py):
    `NemotronHServe`'s state rows, K/V of the attention layers alone and
    the decode step's Mamba token (the in-place kernel where
    `ssm_update.use_kernel` takes it), in the pattern `ME` / `*E` a layer,
    every block's result added through `residual_multiplier`, and a prompt
    walked in slices."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__(cfg)
        self.pattern = blocks(cfg.pattern)
        self.layers = cfg.layers
        self.prompt_slice = cfg.prompt_slice

    def block_params(self, params, kind, i):
        # the expert stacks stay whole: `expert_mlp` addresses them in place
        return block_params(params, kind, i, skip=_EXPERTS)

    def embed(self, params, ids, positions):
        return _embed(params, ids, self.cfg)      # no position anywhere

    def mlp(self, lp, y, params, l):
        lp = dict(lp, **{"blk." + k: params["moe." + k] for k in _EXPERTS})
        return _moe.expert_mlp(lp, y, self.cfg.routing, layer=l)

    def res_out(self, lp, kept, out, which):
        if which == "attn":
            return _proj(lp, out, kept, self.cfg)
        return _add(kept, out, self.cfg)

    # the softmax's scale is the model's: every attention form is told it

    def attend_prompt(self, lp, q, k, v):
        return _decoder.gqa_prompt(q, k, v, self.heads, self.kv_heads,
                                   self.cfg.attention_multiplier)

    def attend_cached(self, lp, q, keys, vals, pos, extra=()):
        return _decoder.mha_cached(q, keys, vals, pos, self.heads,
                                   self.kv_heads,
                                   self.cfg.attention_multiplier)

    def attend_paged(self, lp, q, k_pool, v_pool, layer, block_tables,
                     positions, rated=()):
        from ..ops.pallas import paged_attention as pa

        return pa.paged_gqa_attention(
            q, k_pool, v_pool, layer, block_tables, positions,
            heads=self.heads, kv_heads=self.kv_heads,
            scale=self.cfg.attention_multiplier)

    def attend_slice(self, lp, q, k_pool, v_pool, rated, layer, block_table,
                     start, block_size):
        return _decoder.gqa_slice(
            q, k_pool, v_pool, layer, block_table, start, block_size,
            self.heads, self.kv_heads,
            self.cfg.attention_multiplier), rated

    def ssm_slice(self, lp, y, start, length, state, i, row):
        """One slice of a prompt: from a zero state where `start` is 0, else
        from the row as the slice before left it (the state, and the
        convolution's last K-1 inputs); positions at or past `length` leave
        both as they were."""
        cfg = self.cfg
        conv, pool = state
        with jax.named_scope("state_read"):
            fresh = start == 0
            before = jnp.where(fresh, 0, conv[i, row]).reshape(
                1, cfg.conv_kernel - 1, cfg.conv_dim)
            init = jnp.where(fresh, 0.0, pool[i, row])[None]
        out, tail, s = _nh.mamba_prompt(
            lp, y, jnp.clip(length - start, 0, y.shape[1]), cfg,
            init=(before, init))
        with jax.named_scope("state_write"):
            conv = conv.at[i, row].set(
                tail[0].reshape(conv.shape[2:]).astype(conv.dtype))
            pool = pool.at[i, row].set(s[0])
        return out, (conv, pool)

    def ssm_prompt(self, lp, y, length, state, i, row):
        return self.ssm_slice(lp, y, jnp.int32(0), length, state, i, row)

    @jax.named_scope("head")
    def head(self, params, x, prev_ids, eos_id):
        return _decoder.beam_top1(prev_ids.astype(jnp.int32),
                                  _logits(params, x, self.cfg), eos_id)

    def describe(self) -> Dict:
        cfg = self.cfg
        first, past = cfg.routing.held_range
        return {"layer": "a mixer then experts", "blocks": self.pattern,
                "router_outputs": cfg.n_experts,
                "held_experts": [first, past],
                "multipliers": {
                    "embedding": cfg.embedding_multiplier,
                    "residual": cfg.residual_multiplier,
                    "attention": cfg.attention_multiplier,
                    "logits_scaling": cfg.logits_scaling}}


def _block(kind, lp, x, cfg: GraniteHybridConfig):
    """One block of the full forward pass, x [B, T, hidden]."""
    y = _rms_norm(x, lp["blk.norm.scale"], cfg.rms_eps)
    if kind == "M":
        with jax.named_scope("ssm"):
            out, _, _ = _nh.mamba_prompt(lp, y, None, cfg)
        x = _add(x, out, cfg)
    elif kind == "E":
        out, _ = _moe.expert_mlp(lp, y, cfg.routing)
        x = _add(x, out, cfg)
    else:
        q, k, v = _nh._qkv(lp, y)     # no position enters
        with jax.named_scope("attention"):
            ctx = _decoder.gqa_prompt(q, k, v, cfg.heads, cfg.kv_heads,
                                      cfg.attention_multiplier)
        x = _proj(lp, ctx, x, cfg)
    return shard(x, ("batch", "seq", "embed"))


def apply(params: Params, cfg: GraniteHybridConfig, ids: jax.Array
          ) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab]."""
    adt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = _embed(params, ids, cfg).astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    with jax.named_scope("layers"):
        for kind, i in _decoder.pattern_blocks(blocks(cfg.pattern)):
            x = _block(kind, block_params(params, kind, i), x, cfg)
    with jax.named_scope("head"):
        logits = _logits(params, x, cfg)
    return shard(logits, ("batch", "seq", "vocab"))
