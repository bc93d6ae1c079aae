"""MiniCPM-SALA (`model_type` `minicpm_sala`, openbmb/MiniCPM-SALA
`config.json`): a dense decoder whose layers are a mixer then a SwiGLU, the
mixer of two kinds by the published `mixer_types`. With `a = scale_depth /
sqrt(depth_layers)` (the PUBLISHED depth, whatever the cut):

    x0 = scale_emb * embed(id)
    h  = h + a * Mixer(RMSNorm(h))
    h  = h + a * W_d (silu(W_g y) * W_u y),  y = RMSNorm(h)    eps 1e-6
    logits = head(RMSNorm(h) / (hidden / dim_model_base))

`minicpm4` (`S` in `mixers`): block-sparse softmax attention in the
InfLLM-V2 form. `heads` query heads over `kv_heads` K/V heads, RMSNorm a
head on q and k, NO position encoding, scale 1/sqrt(head_dim). The query
at position t sees n = t + 1 tokens. If n <= `dense_len`: causal softmax
over all of them. Else a K/V head's query heads SELECT together: compressed
keys `kc_j = mean(k[stride j : stride j + kernel])` of every complete
window, `p_h = softmax_j(q_h . kc_j / sqrt(D))`, `P_g = sum_h p_h` over the
group, a block of `sel_block` tokens scores the largest `P_g,j` of the
windows that overlap it, the first `init_blocks` blocks and those that hold
the newest `window` tokens are always taken, the rest of `topk` by score,
and the output is softmax attention over the tokens <= t of the taken
blocks. Then `o * sigmoid(W_gate y)` and `W_o`.

`lightning-attn` (`L`): linear attention with a fixed decay a head. q, k, v
of `lin_heads` x `lin_head_dim`, RMSNorm a head on q and k, rotary on q and
k (rotate-half over all lanes), `S_t = exp(-s_h) S_{t-1} + v_t k_t^T` in
float32, `o_t = S_t q_t / sqrt(D)`, no softmax; `RMSNorm(o) *
sigmoid(W_gate y)`, `W_o`. The recurrence is `ops/ssm.py`'s (x = v, B = k,
C = q, one group a head, dt = 1): the chunked scan for a prompt and the
in-place row update for a token are the ones Mamba-2 runs through.

What a sequence keeps: K and V a token in the sparse layers (the paged
pools, `kv_heads * head_dim` lanes each), a COMPRESSED KEY every `stride`
tokens in a third pool that follows the same block table
(`MiniCPMSALAServe.rated`; entry e is the window that COMPLETES in the
e-th group of `stride` tokens, tokens `stride (e - 1) .. stride (e + 1) -
1`, so a block's entries are written when its tokens are), and a `[heads,
D, D]` float32 state a lightning layer in a row pool.

Behind `models/decoder.py` a layer is two blocks of the pattern: `*E` a
sparse layer, `ME` a lightning layer; the factor `a` is inside the pieces.
A prompt is walked in slices of `prompt_slice` tokens (`decoder
.prefill_sliced`): at 32k tokens one pass would hold float32 scores of
32 x T x T.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm as _ssm
from . import decoder as _decoder
from .common import Params, rms_norm as _rms_norm
from .joyai import NORM_STD

KINDS = {"*": "attn", "M": "lin", "E": "mlp"}
PUBLISHED_MIXERS = "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS"

# Deviation of a seeded sparse-attention score q.k / sqrt(head_dim), through
# the QK-norm gains (`models/nemotron_h.ATTN_SCORE_STD` says why): at 1
# every block scores alike, the 64th and the 65th change places on a bf16
# rounding and a dense walk reads as the right model does.
SCORE_STD = 5.0

_MASKED = -1e30
# rows of queries and tokens of keys a step of a prompt slice's attention
Q_ROWS = 512
KEY_CHUNK = 2048


@dataclasses.dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden: int = 4096
    mlp_dim: int = 16384            # `intermediate_size`
    mixers: str = PUBLISHED_MIXERS  # `mixer_types`: S minicpm4, L lightning
    # S
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    kernel_size: int = 32           # tokens a compressed key averages
    kernel_stride: int = 16         # tokens between two of them
    sel_block: int = 64             # tokens a block of the selection
    topk: int = 64                  # blocks a query reads
    init_blocks: int = 1            # always taken, at the start
    window: int = 2048              # newest tokens always taken
    dense_len: int = 8192           # at or under it a query reads everything
    # L
    lin_heads: int = 32             # `lightning_nh` (= `lightning_nkv`)
    lin_head_dim: int = 128
    rope_theta: float = 10000.0
    chunk: int = 128                # the prompt scan's chunk
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_layers: int = 32          # the PUBLISHED depth, in `a`
    dim_model_base: int = 256
    prompt_slice: int = 2048        # tokens a slice of the prefill's walk
    max_len: int = 524288
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.kernel_size != 2 * self.kernel_stride \
                or self.sel_block % self.kernel_stride:
            raise ValueError(
                "the compressed keys' windows must be two strides long and "
                "a selection block whole strides: kernel "
                f"{self.kernel_size}, stride {self.kernel_stride}, block "
                f"{self.sel_block}")

    @staticmethod
    def tiny() -> "MiniCPMSALAConfig":
        return MiniCPMSALAConfig(
            vocab_size=512, hidden=64, mlp_dim=128, mixers="SLLS", heads=4,
            kv_heads=2, head_dim=16, kernel_size=8, kernel_stride=4,
            sel_block=16, topk=4, init_blocks=1, window=32, dense_len=48,
            lin_heads=4, lin_head_dim=16, chunk=8, prompt_slice=32,
            max_len=192)

    @property
    def pattern(self) -> str:
        """A character a block for `decoder.mixer_layers`: a sparse layer
        is attention then the SwiGLU, a lightning layer a recurrent mixer
        then the SwiGLU."""
        return "".join("*E" if m == "S" else "ME" for m in self.mixers)

    @property
    def layers(self) -> int:
        return len(self.mixers)

    @property
    def depth_scale(self) -> float:
        """`a`: what every block's output is scaled by."""
        return self.scale_depth / math.sqrt(self.depth_layers)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def slopes(self) -> jax.Array:
        """A lightning head forgets by exp(-s_h) a token: Lightning
        Attention-2's `s_h = 2^(-8 (h + 1) / heads)`."""
        h = jnp.arange(1, self.lin_heads + 1, dtype=jnp.float32)
        return jnp.exp2(-8.0 * h / self.lin_heads)

    def serve_model(self) -> "MiniCPMSALAServe":
        """This configuration behind the interface the decode engine
        drives (models/decoder.py)."""
        return MiniCPMSALAServe(self)


_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,),
             "head.w": ("embed", "vocab")}
_KIND_AXES = {
    "*": {"norm.scale": (None,), "wq": ("embed", "heads"),
          "wk": ("embed", None), "wv": ("embed", None),
          "q_norm": (None,), "k_norm": (None,),
          "wg": ("embed", "heads"), "wo": ("heads", "embed")},
    "M": {"norm.scale": (None,), "wq": ("embed", "heads"),
          "wk": ("embed", "heads"), "wv": ("embed", "heads"),
          "q_norm": (None,), "k_norm": (None,), "onorm.scale": (None,),
          "wg": ("embed", "heads"), "wo": ("heads", "embed")},
    "E": {"norm.scale": (None,), "w_gate": ("embed", "mlp"),
          "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")},
}


def init_layer(rng: jax.Array, cfg: MiniCPMSALAConfig, l, kind=None
               ) -> Params:
    """Block `l` (counted over the whole pattern) of `init(rng, cfg)`
    alone, in float32 and under the prefix `blk.`: every block has a key of
    its own, so that the float32 set (11.3 GB at the cell's cut) is never
    whole on the device (`olmoe.init_layer`'s way). Every matrix is normal
    at 1/sqrt(fan_in) (the factor `a` of the residual is the model's, not
    the draw's), norm gains 1 + `NORM_STD` x normal, and the sparse layers'
    QK-norm gains sqrt(`SCORE_STD`) times that (a score is a product of
    the two)."""
    kind = kind or cfg.pattern[l]
    H = cfg.hidden
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), l), 12))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def gains(n, at=1.0):
        return at * (1.0 + normal((n,), NORM_STD))

    a = math.sqrt(1.0 / H)
    lp = {"blk.norm.scale": gains(H)}
    if kind == "*":
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        peak = math.sqrt(SCORE_STD)
        lp.update({
            "blk.wq": normal((H, q), a), "blk.wk": normal((H, kv), a),
            "blk.wv": normal((H, kv), a),
            "blk.q_norm": gains(cfg.head_dim, peak),
            "blk.k_norm": gains(cfg.head_dim, peak),
            "blk.wg": normal((H, q), a),
            "blk.wo": normal((q, H), math.sqrt(1.0 / q)),
        })
    elif kind == "M":
        w = cfg.lin_heads * cfg.lin_head_dim
        lp.update({
            "blk.wq": normal((H, w), a), "blk.wk": normal((H, w), a),
            "blk.wv": normal((H, w), a),
            "blk.q_norm": gains(cfg.lin_head_dim),
            "blk.k_norm": gains(cfg.lin_head_dim),
            "blk.onorm.scale": gains(w),
            "blk.wg": normal((H, w), a),
            "blk.wo": normal((w, H), math.sqrt(1.0 / w)),
        })
    elif kind == "E":
        M = cfg.mlp_dim
        lp.update({
            "blk.w_gate": normal((H, M), a), "blk.w_up": normal((H, M), a),
            "blk.w_down": normal((M, H), math.sqrt(1.0 / M)),
        })
    else:
        raise ValueError(f"unknown block kind {kind!r} in the pattern")
    return lp


def init_top(rng: jax.Array, cfg: MiniCPMSALAConfig) -> Params:
    """The parameters outside the blocks, in float32. The head is drawn at
    (hidden / dim_model_base) / sqrt(hidden): muP divides the head's input
    by hidden / dim_model_base and a trained head makes up for it; at the
    plain draw the logits' deviation would be 1/16 and every token a near
    tie."""
    k_emb, k_head, k_norm = jax.random.split(jax.random.fold_in(rng, 0), 3)
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32) * 0.02,
        "ln_f.scale": 1.0 + NORM_STD * jax.random.normal(
            k_norm, (H,), jnp.float32),
        "head.w": jax.random.normal(k_head, (H, V), jnp.float32)
        * (H / cfg.dim_model_base) * math.sqrt(1.0 / H),
    }


def kind_layers(cfg: MiniCPMSALAConfig, kind: str):
    """The pattern's positions of the blocks of `kind`, in order."""
    return [l for l, c in enumerate(cfg.pattern) if c == kind]


def init(rng: jax.Array, cfg: MiniCPMSALAConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """The blocks of a kind stacked under the kind's prefix (`attn.`,
    `lin.`, `mlp.`), made one block and one tensor at a time and cast to
    `dtype` as each is made."""
    params = {k: v.astype(dtype) for k, v in init_top(rng, cfg).items()}
    axes = dict(_TOP_AXES)
    for kind, prefix in KINDS.items():
        where = jnp.asarray(kind_layers(cfg, kind), jnp.int32)
        if not where.size:
            continue
        for name, ax in _KIND_AXES[kind].items():
            params[f"{prefix}.{name}"] = jax.lax.map(
                lambda l: init_layer(rng, cfg, l, kind)["blk." + name]
                .astype(dtype), where)
            axes[f"{prefix}.{name}"] = ("layer",) + ax
    return params, axes


def block_params(params: Params, kind: str, i: int) -> Params:
    """Block `i` of its kind out of the flat set, under `blk.`."""
    prefix = KINDS[kind] + "."
    return {"blk." + k[len(prefix):]: v[i] for k, v in params.items()
            if k.startswith(prefix)}


# Layer scopes: `ln`; a sparse layer's `qkv`, `kv_write`, `attention`
# (holding `kc_write`: the compressed key a token or a slice completes, and
# `select`: the compressed keys' scores, in a decode step on a TPU by the
# kernel that reads them where they lie, the top-k and the pairs' tables),
# `proj`; a lightning layer's `ssm` (holding `ssm_in`, `scan`, `state_read`
# / `state_write`, `ssm_out`); `mlp`; `head`. tests/test_minicpm_sala.py
# holds the list.


def _head_norm(x, gain, heads: int, eps: float):
    """RMSNorm a head: x [..., heads * D] -> [..., heads, D] float32."""
    x = x.astype(jnp.float32).reshape(x.shape[:-1] + (heads, -1))
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def rope_half(x, positions, theta: float):
    """Rotate-half over all of the last dimension: x [..., heads, D]
    float32 at `positions` [...]."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -- the selection ----------------------------------------------------------


def top_mask(score, exists, k: int):
    """The `k` largest of `score` `[..., N]` (float32, >= 0) among the
    entries that `exists`, as a mask; ties go to the lower index; fewer
    than `k` exist: all of them. No sort: the k-th largest value is found
    bit by bit (the bits of a non-negative float order as it does), 31
    counts over the row, which is what a query row a token can afford (a
    sort of 768 scores a query row and K/V head was a quarter of a 32k
    prompt's prefill: PERF.md section 6, PR 43)."""
    bits = jnp.where(exists, jax.lax.bitcast_convert_type(
        jnp.maximum(score, 0.0).astype(jnp.float32), jnp.int32), -1)

    def refine(i, kth):
        cand = kth | jnp.left_shift(jnp.int32(1), 30 - i)
        enough = jnp.sum(bits >= cand, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(
        0, 31, refine, jnp.zeros(bits.shape[:-1] + (1,), jnp.int32))
    above = bits > kth
    ties = bits == kth
    left = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                            <= left))


def block_scores(cfg: MiniCPMSALAConfig, q, kc, n, block_size: int):
    """What the selection ranks the blocks by. q `[S, kv_heads, group, D]`
    (normalised, unscaled), `kc` the sequences' compressed keys as they lie
    in their pool's rows through the table, `[S, MB, E * W]` (or `[MB, E *
    W]`: one sequence all the queries share), E = block_size / stride
    entries a cache block of W = kv_heads * D lanes; `n` `[S]` the tokens
    each query sees (its position + 1). A K/V head's query heads each take
    a softmax over the windows the query sees and sum it; a selection
    block scores the largest sum of the windows that overlap it ->
    `[S, kv_heads, NSB]` float32 (on a TPU a decode step reads the same
    from the pool where it lies: `paged_attention.paged_select_scores`)."""
    f32 = jnp.float32
    S, G, R, D = q.shape
    stride, sb = cfg.kernel_stride, cfg.sel_block
    E = block_size // stride
    MB = kc.shape[-2]
    per_sel = sb // block_size              # cache blocks a selection block
    if sb % block_size or MB % per_sel:
        raise ValueError(
            f"a selection block of {sb} tokens must be whole cache blocks "
            f"of {block_size}, and the table whole selection blocks")
    NSB = MB // per_sel
    shared = kc.ndim == 2
    scale = 1.0 / math.sqrt(D)
    sc = []
    for e in range(E):
        per_head = []
        for g in range(G):
            lanes = kc[..., (e * G + g) * D:(e * G + g + 1) * D]
            per_head.append(jnp.einsum(
                "srd,md->srm" if shared else "srd,smd->srm",
                q[:, g].astype(kc.dtype), lanes,
                preferred_element_type=f32))
        sc.append(jnp.stack(per_head, axis=1))
    sc = jnp.stack(sc) * scale                              # [E, S, G, R, MB]
    # entry `ent` is the window that completes in the ent-th group of
    # `stride` tokens: none completes in the first, and a query sees the
    # windows whose last token it sees
    ent = (jnp.arange(MB, dtype=jnp.int32)[None, :] * E
           + jnp.arange(E, dtype=jnp.int32)[:, None])       # [E, MB]
    seen = (ent[:, None, :] >= 1) \
        & (ent[:, None, :] < (n // stride)[None, :, None])  # [E, S, MB]
    seen = seen[:, :, None, None, :]
    sc = jnp.where(seen, sc, _MASKED)
    top = jnp.max(sc, axis=(0, 4), keepdims=True)
    p = jnp.where(seen, jnp.exp(sc - top), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=(0, 4), keepdims=True), 1e-30)
    P = jnp.sum(p, axis=3)                                  # [E, S, G, MB]
    # a window overlaps the cache block it completes in and, the first of
    # a block alone, the block before
    first_of_next = jnp.pad(P[0][..., 1:], [(0, 0), (0, 0), (0, 1)])
    score = jnp.maximum(jnp.max(P, axis=0), first_of_next)  # [S, G, MB]
    return jnp.max(score.reshape(S, G, NSB, per_sel), axis=-1)


def take_blocks(cfg: MiniCPMSALAConfig, score, n):
    """The taken blocks of `score` `[S, kv_heads, NSB]` (`block_scores`)
    for queries that see `n` `[S]` tokens, as a mask: the first
    `init_blocks` and those that hold the newest `window` tokens always,
    the rest of min(topk, the blocks that exist) by score; ties go to the
    lower index."""
    sb = cfg.sel_block
    NSB = score.shape[-1]
    b = jnp.arange(NSB, dtype=jnp.int32)[None, :]
    exists = b <= ((n - 1) // sb)[:, None]
    forced = (b < cfg.init_blocks) \
        | (b >= (jnp.maximum(n - cfg.window, 0) // sb)[:, None])
    # a group's sum is at most its heads: the forced blocks stand above
    score = jnp.where(forced[:, None, :], 1e4, score)
    return top_mask(score, jnp.broadcast_to(exists[:, None, :], score.shape),
                    min(cfg.topk, NSB))


def select_blocks(cfg: MiniCPMSALAConfig, q, kc, n, block_size: int):
    """Which selection blocks each query reads: `take_blocks` of
    `block_scores` (their arguments) -> a mask `[S, kv_heads, NSB]`,
    min(topk, the blocks that exist) of them a row. A K/V head's query
    heads select together."""
    return take_blocks(cfg, block_scores(cfg, q, kc, n, block_size), n)


def taken_indices(mask, k: int):
    """A mask `[..., N]` of at most `k` taken blocks as their indices in
    ascending order `[..., k]`, `N` (past the last) where fewer are
    taken. No sort and no gather: the i-th taken block is as many blocks
    in as there are blocks with at most i taken up to and with them, and
    that count is a product with a triangle (0 and 1 in bf16, summed in
    float32: exact)."""
    N = mask.shape[-1]
    at = jnp.arange(N, dtype=jnp.int32)
    upto = jnp.dot(mask.astype(jnp.bfloat16),
                   (at[:, None] <= at[None, :]).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    return jnp.sum(upto[..., None, :]
                   <= jnp.arange(k, dtype=jnp.int32)[:, None],
                   axis=-1, dtype=jnp.int32)


class MiniCPMSALAServe(_decoder.ServeModel):
    """The blocks for the serve programs (models/decoder.py): `*E` a sparse
    layer and `ME` a lightning layer, K/V and compressed keys for the
    sparse layers, a state row a lightning layer."""

    def __init__(self, cfg: MiniCPMSALAConfig):
        self.cfg = cfg
        self.pattern = cfg.pattern
        self.layers, self.heads = len(cfg.pattern), cfg.heads
        self.head_dim = cfg.head_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len
        self.prompt_slice = cfg.prompt_slice

    @property
    def kv_heads(self):
        return self.cfg.kv_heads

    @property
    def kv_layers(self):
        return self.cfg.count("*")

    @property
    def rated(self):
        """One compressed key (all K/V heads side by side) every
        `kernel_stride` tokens a sparse layer."""
        return ((self.cfg.kv_heads * self.cfg.head_dim,
                 self.cfg.kernel_stride),)

    def state_pools(self, rows: int, dtype):
        """Per lightning layer and row the `[heads, D, D]` state in
        float32: slow heads decay by exp(-2^-8) a token, and in bf16 the
        additions under 1/256 of a value are lost."""
        cfg, n = self.cfg, self.cfg.count("M")
        if not n:
            return ()
        return (((n, rows, cfg.lin_heads, cfg.lin_head_dim,
                  cfg.lin_head_dim), jnp.float32),)

    def block_params(self, params, kind, i):
        return block_params(params, kind, i)

    def embed(self, params, ids, positions):
        return params["wte.w"][ids] * self.cfg.scale_emb

    def norm(self, lp, h):
        return _rms_norm(h, lp["blk.norm.scale"], self.cfg.rms_eps)

    # -- the SwiGLU ---------------------------------------------------------

    @jax.named_scope("mlp")
    def mlp(self, lp, y, params, l):
        up = jax.nn.silu(y @ lp["blk.w_gate"].astype(y.dtype)) \
            * (y @ lp["blk.w_up"].astype(y.dtype))
        out = up @ lp["blk.w_down"].astype(y.dtype)
        return out * jnp.asarray(self.cfg.depth_scale, y.dtype), None

    # -- the sparse layers --------------------------------------------------

    @jax.named_scope("qkv")
    def qkv(self, lp, y, positions):
        """(q with the output gate's logits behind it, k, v): q and k
        normalised a head; no position enters."""
        cfg = self.cfg
        q = _head_norm(y @ lp["blk.wq"].astype(y.dtype), lp["blk.q_norm"],
                       cfg.heads, cfg.rms_eps)
        k = _head_norm(y @ lp["blk.wk"].astype(y.dtype), lp["blk.k_norm"],
                       cfg.kv_heads, cfg.rms_eps)
        lead = y.shape[:-1]
        gate = y @ lp["blk.wg"].astype(y.dtype)
        return (jnp.concatenate([q.reshape(lead + (-1,)).astype(y.dtype),
                                 gate], axis=-1),
                k.reshape(lead + (-1,)).astype(y.dtype),
                y @ lp["blk.wv"].astype(y.dtype))

    def _ungate(self, q):
        w = self.cfg.heads * self.cfg.head_dim
        return q[..., :w], q[..., w:]

    @staticmethod
    def _gated(ctx, gate):
        return (ctx.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)

    @jax.named_scope("proj")
    def proj(self, lp, ctx, res):
        return res + (ctx @ lp["blk.wo"].astype(ctx.dtype)) \
            * jnp.asarray(self.cfg.depth_scale, ctx.dtype)

    def paged_route(self, x, k_pool, v_pool):
        from ..ops.pallas import paged_attention as pa

        return "paged_sparse" if pa.use_paged_sparse(
            x, k_pool, self.heads, self.kv_heads) else None

    def rated_tables(self, x, tables, rated, positions, block_size):
        """How a decode step reads the compressed keys, said and counted
        once a step: where the kernel takes them
        (`paged_attention.use_paged_select`) the tables with what its
        walk goes by counted (`paged_attention.with_rows`, under the scope
        `select` as all of the selection is), which is what `attend_paged`
        goes by; else the tables as they are, and the gather of every
        slot's whole table."""
        from ..ops.pallas import paged_attention as pa

        cfg = self.cfg
        paged = tables is not None and pa.use_paged_select(
            x, rated[0], cfg.heads, cfg.kv_heads,
            block_size // cfg.kernel_stride, cfg.sel_block // block_size,
            tables.ids.shape[1])
        pa.GATE_COUNTS["select_paged" if paged else "select_gather"] += 1
        if not paged:
            return tables
        with jax.named_scope("select"):
            return pa.with_rows(tables, positions, block_size)

    def store_token(self, lp, k_pool, rated, layer, block_tables, positions,
                    block_size):
        """The compressed key a decode step completes: where position p
        ends a group of `stride` tokens, entry p // stride, the mean of the
        `kernel` newest keys, read back through the table."""
        from ..serving import kv_cache as kvc

        cfg = self.cfg
        (pool,) = rated
        with jax.named_scope("kc_write"):
            due = (positions + 1) % cfg.kernel_stride == 0
            at = jnp.maximum(
                positions[:, None] - cfg.kernel_size + 1
                + jnp.arange(cfg.kernel_size, dtype=jnp.int32)[None, :], 0)
            blk = jnp.take_along_axis(block_tables, at // block_size, axis=1)
            keys = k_pool[layer, blk, at % block_size]      # [S, kernel, W]
            entry = jnp.mean(keys.astype(jnp.float32), axis=1)
            pool = kvc.write_token_rated(
                pool, layer, entry, block_tables,
                positions // cfg.kernel_stride, due,
                block_size // cfg.kernel_stride)
        return (pool,)

    def _selection(self, q, kc, n, block_size):
        """`select_blocks` for the query rows q `[S, heads * D]`."""
        cfg = self.cfg
        with jax.named_scope("select"):
            qh = q.reshape(q.shape[0], cfg.kv_heads,
                           cfg.heads // cfg.kv_heads, cfg.head_dim)
            return select_blocks(cfg, qh, kc, n, block_size)

    def scores_where_they_lie(self, q, pool, layer, tables, positions,
                              block_size, interpret=False):
        """`block_scores` of the query rows q `[S, heads * D]` from the
        pool of compressed keys through `tables`
        (`paged_attention.with_rows`): by the kernel that walks the live
        pieces (`paged_select_scores`), or, in a step whose tables are
        too many pieces for that to pay (`Tables.few`: a pool that has
        fragmented), by the gather of every slot's whole table; the same
        scores either way, chosen in the program by what the tables
        say."""
        from ..ops.pallas import paged_attention as pa
        from ..serving import kv_cache as kvc

        cfg = self.cfg
        S = q.shape[0]

        def gathered():
            return block_scores(
                cfg, q.reshape(S, cfg.kv_heads, cfg.heads // cfg.kv_heads,
                               cfg.head_dim),
                kvc.gather_rated(pool, layer, tables.ids), positions + 1,
                block_size)

        return jax.lax.cond(tables.few, lambda: pa.paged_select_scores(
            q, pool, layer, tables, positions, kv_heads=cfg.kv_heads,
            stride=cfg.kernel_stride, block_size=block_size,
            interpret=interpret), gathered)

    def attend_paged(self, lp, q, k_pool, v_pool, layer, block_tables,
                     positions, rated=()):
        """Selection, then the walk over the taken blocks: every (slot,
        K/V head) gets a table of its own, the blocks it reads in
        ascending order (a row at or under `dense_len`: its own table),
        and the position of its newest token in that list; the walk takes
        a slot's K/V heads together and fetches once what their lists
        share (`paged_attention.pair_lists`). The blocks' scores come
        from the compressed keys where they lie (`scores_where_they_lie`)
        where the step's tables carry the pieces of that walk
        (`Tables.rows`: `rated_tables` found its gate open), else from a
        gather of every slot's whole table of them; one `take_blocks`
        either way."""
        from ..ops.pallas import paged_attention as pa
        from ..serving import kv_cache as kvc

        cfg = self.cfg
        q, gate = self._ungate(q)
        block_tables = pa.with_runs(block_tables, k_pool, v_pool)
        ids = block_tables.ids
        S, MB = ids.shape
        bs = k_pool.shape[2]
        G, sb = cfg.kv_heads, cfg.sel_block
        per_sel = sb // bs
        n = positions + 1
        if block_tables.rows is not None:
            with jax.named_scope("select"):
                mask = take_blocks(cfg, self.scores_where_they_lie(
                    q, rated[0], layer, block_tables, positions, bs), n)
        else:
            with jax.named_scope("select"):
                kc = kvc.gather_rated(rated[0], layer, ids)
            mask = self._selection(q, kc, n, bs)
        with jax.named_scope("select"):
            K = min(cfg.topk, mask.shape[-1])
            idx = taken_indices(mask, K)
            count = jnp.sum(mask, axis=-1, dtype=jnp.int32)
            dense_blocks = min(MB, -(-cfg.dense_len // bs))
            width = max(K * per_sel, dense_blocks)
            at = (idx[..., None] * per_sel
                  + jnp.arange(per_sel, dtype=jnp.int32)).reshape(S, G, -1)
            # the table's entry `at`, the null block past its end: one
            # compare a (taken, table) pair, cheaper than a gather of ids
            taken = jnp.sum(jnp.where(
                at[..., None] == jnp.arange(MB, dtype=jnp.int32),
                ids[:, None, None, :], 0), axis=-1, dtype=jnp.int32)
            taken = jnp.pad(taken, [(0, 0), (0, 0),
                                    (0, width - K * per_sel)])
            own = jnp.pad(ids[:, :dense_blocks],
                          [(0, 0), (0, width - dense_blocks)])
            sparse = (n > cfg.dense_len)[:, None]
            tables = jnp.where(sparse[..., None], taken, own[:, None, :])
            newest = jnp.where(
                sparse, (count - 1) * sb + (positions % sb)[:, None],
                positions[:, None])
        # the lists' runs and the entries a slot's K/V heads share (the
        # forced blocks at least; every entry of a row that reads its own
        # table): a compare of the lists, which are there
        lists = pa.pair_lists(tables.reshape(S * G, width),
                              newest.reshape(S * G), G, bs)
        ctx = pa.paged_sparse_attention(
            q, k_pool, v_pool, layer, lists, heads=cfg.heads, kv_heads=G,
            list_tokens=K * sb)
        return self._gated(ctx, gate)

    def attend_cached(self, lp, q, keys, vals, pos, extra=()):
        """The gathered form of a decode step (off the TPU): the same
        selection, as a mask over the gathered tokens. One query row a
        slot (`prefill_chunk` and `verify_step` are refused for a model
        with state rows)."""
        cfg = self.cfg
        S, W, _ = q.shape
        if W != 1:
            raise ValueError("block-sparse attention over a gathered "
                             "context takes one query row a slot")
        q, gate = self._ungate(q[:, 0])
        M = keys.shape[1]
        bs = M // extra[0].shape[1]
        n = pos[:, 0] + 1
        taken = self._selection(q, extra[0], n, bs)         # [S, G, NSB]
        G, R, D = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
        tok = jnp.arange(M, dtype=jnp.int32)
        taken = jnp.repeat(taken, cfg.sel_block, axis=-1)
        mask = (tok[None, None, :] <= pos[:, :1, None]) \
            & (taken | (n <= cfg.dense_len)[:, None, None])
        scores = jnp.einsum("sgrd,smgd->sgrm", q.reshape(S, G, R, D),
                            keys.reshape(S, M, G, D),
                            preferred_element_type=jnp.float32) \
            * (1.0 / math.sqrt(D))
        att = jax.nn.softmax(
            jnp.where(mask[:, :, None, :], scores, _MASKED), axis=-1)
        ctx = jnp.einsum("sgrm,smgd->sgrd", att.astype(vals.dtype),
                         vals.reshape(S, M, G, D))
        return self._gated(ctx.reshape(S, -1), gate)[:, None]

    def attend_slice(self, lp, q, k_pool, v_pool, rated, layer, block_table,
                     start, block_size):
        """One slice of a prompt against the cache so far: the slice's
        compressed keys are written, then `Q_ROWS` queries at a time select
        (a query over `dense_len` tokens) and attend, the keys walked in
        chunks of `KEY_CHUNK` tokens up to the queries' own with an online
        softmax: float32 scores of `heads x Q_ROWS x KEY_CHUNK` whatever
        the prompt's length. Every token of a taken block is COMPUTED and
        masked; a kernel that skips the blocks not taken is not here."""
        from ..serving import kv_cache as kvc

        cfg = self.cfg
        f32 = jnp.float32
        q, gate = self._ungate(q[0])                        # [C, heads * D]
        C = q.shape[0]
        G, R, D = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
        stride, sb = cfg.kernel_stride, cfg.sel_block
        keys = kvc.gather_kv(k_pool, layer, block_table[None])[0]
        vals = kvc.gather_kv(v_pool, layer, block_table[None])[0]
        M = keys.shape[0]
        (pool,) = rated
        with jax.named_scope("kc_write"):
            # the window that completes in group i of the slice averages
            # that group and the one before it (none before the first
            # token: entry 0 is never read)
            before = jax.lax.dynamic_slice_in_dim(
                keys, jnp.maximum(start - stride, 0), stride)
            own = jax.lax.dynamic_slice_in_dim(keys, start, C)
            groups = jnp.concatenate([before, own]).astype(f32).reshape(
                C // stride + 1, stride, -1).mean(axis=1)
            entries = 0.5 * (groups[:-1] + groups[1:])
            blocks = jax.lax.dynamic_slice_in_dim(
                block_table, start // block_size, C // block_size)
            pool = kvc.write_blocks_rated(pool, layer, entries, blocks)
        with jax.named_scope("select"):
            kc = kvc.gather_rated(pool, layer, block_table[None])[0]
        rows = min(Q_ROWS, C)
        chunk = min(KEY_CHUNK, M)
        if C % rows or M % chunk or chunk % sb:
            raise ValueError(
                f"a slice of {C} queries over a table of {M} tokens needs "
                f"whole steps of {rows} queries and {chunk} keys")
        NSB = M // sb
        keys = keys.reshape(M, G, D)
        vals = vals.reshape(M, G, D)

        def step(first):
            """`rows` queries from the slice's row `first` on."""
            qs = jax.lax.dynamic_slice_in_dim(q, first, rows)
            t = start + first + jnp.arange(rows, dtype=jnp.int32)
            # queries at or under `dense_len` read everything: a step that
            # holds no other (a prompt's first `dense_len` tokens) scores
            # no compressed key
            taken = jax.lax.cond(
                start + first + rows > cfg.dense_len,
                lambda: self._selection(qs, kc, t + 1, block_size)
                | (t + 1 <= cfg.dense_len)[:, None, None],
                lambda: jnp.ones((rows, G, NSB), bool))     # [rows, G, NSB]
            qh = qs.reshape(rows, G, R, D)

            def one(c, carry):
                m, l, acc = carry
                kch = jax.lax.dynamic_slice_in_dim(keys, c * chunk, chunk)
                vch = jax.lax.dynamic_slice_in_dim(vals, c * chunk, chunk)
                sc = jnp.einsum("qgrd,kgd->qgrk", qh, kch,
                                preferred_element_type=f32) \
                    * (1.0 / math.sqrt(D))
                tok = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
                ok = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                    taken, c * (chunk // sb), chunk // sb, axis=2),
                    sb, axis=2) & (tok[None, None, :] <= t[:, None, None])
                sc = jnp.where(ok[:, :, None, :], sc, _MASKED)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(ok[:, :, None, :], jnp.exp(sc - m_new), 0.0)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = alpha * acc + jnp.einsum(
                    "qgrk,kgd->qgrd", p.astype(vch.dtype), vch,
                    preferred_element_type=f32)
                return m_new, l, acc

            chunks = (start + first + rows + chunk - 1) // chunk
            _, l, acc = jax.lax.fori_loop(
                0, chunks, one,
                (jnp.full((rows, G, R, 1), _MASKED, f32),
                 jnp.zeros((rows, G, R, 1), f32),
                 jnp.zeros((rows, G, R, D), f32)))
            return (acc / jnp.maximum(l, 1e-30)).reshape(rows, -1)

        ctx = jax.lax.map(step, jnp.arange(0, C, rows, dtype=jnp.int32))
        ctx = ctx.reshape(C, -1).astype(q.dtype)
        return self._gated(ctx, gate)[None], (pool,)

    # -- the lightning layers -----------------------------------------------

    def _lin_inputs(self, lp, y, positions, pin_v: bool = False):
        """(q scaled, k, v `[..., heads, D]` in y's dtype, the gate's
        logits): q and k normalised a head and rotated to `positions`.
        `pin_v`: v's projection is kept a plain matmul. The row update's
        kernel takes v transposed (a head a lane), and XLA otherwise folds
        that into the projection and transposes W_v, 33 MB of weights a
        layer a step, where 32 rows of activations would do (4% of a
        step: chip run of PR 43)."""
        cfg = self.cfg
        with jax.named_scope("ssm_in"):
            nh = cfg.lin_heads
            q = _head_norm(y @ lp["blk.wq"].astype(y.dtype),
                           lp["blk.q_norm"], nh, cfg.rms_eps)
            k = _head_norm(y @ lp["blk.wk"].astype(y.dtype),
                           lp["blk.k_norm"], nh, cfg.rms_eps)
            q = rope_half(q, positions, cfg.rope_theta) \
                * (1.0 / math.sqrt(cfg.lin_head_dim))
            k = rope_half(k, positions, cfg.rope_theta)
            v = y @ lp["blk.wv"].astype(y.dtype)
            if pin_v:
                v = jax.lax.optimization_barrier(v)
            v = v.reshape(y.shape[:-1] + (nh, cfg.lin_head_dim))
            gate = y @ lp["blk.wg"].astype(y.dtype)
        return q.astype(y.dtype), k.astype(y.dtype), v, gate

    @jax.named_scope("ssm_out")
    def _lin_out(self, lp, o, gate):
        """o `[..., heads, D]` float32: one norm over all of it, the
        sigmoid gate, the output projection and `a`."""
        cfg = self.cfg
        o = o.reshape(o.shape[:-2] + (-1,))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_eps) \
            * lp["blk.onorm.scale"].astype(jnp.float32)
        o = (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)
        return (o @ lp["blk.wo"].astype(o.dtype)) \
            * jnp.asarray(cfg.depth_scale, o.dtype)

    def ssm_slice(self, lp, y, start, length, state, i, row):
        cfg = self.cfg
        (pool,) = state
        C = y.shape[1]
        positions = (start + jnp.arange(C, dtype=jnp.int32))[None]
        q, k, v, gate = self._lin_inputs(lp, y, positions)
        dt, A, D = _ssm.linear_attention_args(cfg.slopes(),
                                              positions < length)
        with jax.named_scope("state_read"):
            init = jnp.where(start > 0, pool[i, row], 0.0)[None]
        with jax.named_scope("scan"):
            out, last = _ssm.ssd_chunked(v, dt, A, k, q, D, cfg.chunk, init)
        with jax.named_scope("state_write"):
            pool = pool.at[i, row].set(last[0])
        return self._lin_out(lp, out, gate), (pool,)

    def ssm_prompt(self, lp, y, length, state, i, row):
        return self.ssm_slice(lp, y, jnp.int32(0), length, state, i, row)

    def ssm_token(self, lp, y, state, i, rows, positions=None):
        """One token a slot: the rows advanced where they lie by the
        kernel of ops/pallas/ssm_update.py on a TPU (told the heads' fixed
        decay), gathered, advanced and scattered back elsewhere."""
        from ..ops.pallas import ssm_update as su

        cfg = self.cfg
        (pool,) = state
        kernel = su.use_kernel(y, pool, cfg.lin_heads)
        su.GATE_COUNTS["kernel" if kernel else "xla"] += 1
        q, k, v, gate = self._lin_inputs(lp, y, positions, pin_v=kernel)
        dt, A, D = _ssm.linear_attention_args(
            cfg.slopes(), jnp.ones(rows.shape, bool))
        if kernel:
            with jax.named_scope("scan"):
                out, pool = su.state_update(
                    pool, jnp.int32(i), rows, jnp.exp(dt * A),
                    v.astype(jnp.float32), k, q)
        else:
            with jax.named_scope("state_read"):
                s = pool[i, rows]
            with jax.named_scope("scan"):
                out, s = _ssm.ssd_step(s, v, dt, A, k, q, D)
            with jax.named_scope("state_write"):
                pool = pool.at[i, rows].set(s)
        return self._lin_out(lp, out, gate), (pool,)

    # -- the head and the step's counters -----------------------------------

    @jax.named_scope("head")
    def head(self, params, x, prev_ids, eos_id):
        cfg = self.cfg
        x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps) \
            * jnp.asarray(cfg.dim_model_base / cfg.hidden, x.dtype)
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        return _decoder.beam_top1(prev_ids.astype(jnp.int32), logits, eos_id)

    def step_counters(self, positions, block_tables):
        """Of one decode step: live rows over `dense_len` (a sparse read a
        sparse layer), the tokens the other live rows read, the blocks a
        sparse row reads a layer, of the live rows' blocks those that
        every K/V head reads whatever it scores (the first `init_blocks`
        and the window's: what the walk fetches once for all heads at the
        least; a row that reads its own table, every block it holds), the
        compressed keys a sparse row scores a layer; all follow from the
        positions."""
        cfg = self.cfg
        n = positions + 1
        sb = cfg.sel_block
        live = block_tables[:, 0] != 0
        sparse = live & (n > cfg.dense_len)
        held = (n - 1) // sb + 1
        blocks = jnp.minimum(cfg.topk, held)
        window_from = jnp.maximum(n - cfg.window, 0) // sb
        forced = jnp.minimum(
            jnp.minimum(cfg.init_blocks, window_from) + held - window_from,
            blocks)
        return {
            "sparse_rows": jnp.sum(sparse, dtype=jnp.int32),
            "dense_tokens": jnp.sum(jnp.where(live & ~sparse, n, 0),
                                    dtype=jnp.int32),
            "blocks_selected": jnp.sum(jnp.where(sparse, blocks, 0),
                                       dtype=jnp.int32),
            "shared_entries": jnp.sum(
                jnp.where(sparse, forced, jnp.where(live, held, 0)),
                dtype=jnp.int32),
            "kc_entries": jnp.sum(
                jnp.where(sparse, n // cfg.kernel_stride - 1, 0),
                dtype=jnp.int32)}

    def step_facts(self, stats) -> Dict:
        if stats is None:
            return {}
        _, c = stats
        rows = int(c["sparse_rows"])
        return {"sparse_rows": rows,
                # tokens the live rows at or under `dense_len` read a layer
                "dense_tokens": int(c["dense_tokens"]),
                # mean blocks a sparse row reads a sparse layer
                "blocks_selected": float(c["blocks_selected"]) / rows
                if rows else 0.0,
                # of the live rows' lists, the selection blocks every K/V
                # head reads (the walk's both-heads copy), summed
                "shared_entries": int(c["shared_entries"]),
                # compressed keys the step's sparse rows score a layer
                "kc_entries": int(c["kc_entries"])}
