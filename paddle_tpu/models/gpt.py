"""GPT (decoder-only transformer), optionally Mixture-of-Experts.

No single reference counterpart (the reference predates LLMs) but composes
reference capabilities the TPU way: stacked per-layer params scanned by
lax.scan (fast compiles), causal flash/ring attention (ops/pallas), GPipe
pipeline over 'pp' (parallel/pipeline.py — the reference's PipelineTrainer),
Switch-style top-1 MoE sharded over 'ep'. This is the model that exercises
ALL five mesh axes (dp/tp/pp/sp/ep) in __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from . import decoder as _decoder
from .common import ParamStore, Params, layer_norm as _ln_named, gelu


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    n_experts: int = 0          # 0 = dense MLP; >0 = Switch top-1 MoE
    capacity_factor: float = 1.25
    dtype: str = "bfloat16"

    @staticmethod
    def tiny(n_experts: int = 0) -> "GPTConfig":
        return GPTConfig(vocab_size=512, hidden=64, layers=4, heads=4,
                         mlp_dim=128, max_len=128, n_experts=n_experts)

    @property
    def head_dim(self):
        return self.hidden // self.heads

    def serve_model(self) -> "GPTServe":
        """This configuration behind the interface the decode engine
        drives (models/decoder.py)."""
        return GPTServe(self)

    def train_flops_per_token(self, seq_len: int) -> float:
        H, M, L = self.hidden, self.mlp_dim, self.layers
        # top-1 MoE routes each token through exactly one expert, so its
        # per-token matmul FLOPs equal the dense MLP (router cost omitted)
        mlp = 2 * H * M
        per_layer = 4 * H * H + mlp + 2 * seq_len * H  # qkvo + mlp + attn
        return 3 * 2 * (L * per_layer + self.vocab_size * H)


def init(rng: jax.Array, cfg: GPTConfig) -> Tuple[Params, Dict]:
    """Layer params are STACKED on a leading [L] axis (scan/pipeline)."""
    s = ParamStore(rng, jnp.float32)
    s.embedding("wte", cfg.vocab_size, cfg.hidden, axes=("vocab", "embed"))
    s.embedding("wpe", cfg.max_len, cfg.hidden, axes=(None, "embed"))

    L, H, M = cfg.layers, cfg.hidden, cfg.mlp_dim

    def stacked(key, shape, scale, axes):
        s.add(key, jax.random.normal(s.next_rng(), (L,) + shape,
                                     jnp.float32) * scale, ("layer",) + axes)

    a = math.sqrt(2.0 / (H + H))
    stacked("blk.ln1.scale", (H,), 0.0, (None,))
    s.params["blk.ln1.scale"] += 1.0
    stacked("blk.ln1.bias", (H,), 0.0, (None,))
    stacked("blk.wqkv", (H, 3 * H), a, ("embed", "heads"))
    stacked("blk.bqkv", (3 * H,), 0.0, ("heads",))
    stacked("blk.wo", (H, H), a / math.sqrt(2 * L), ("heads", "embed"))
    stacked("blk.bo", (H,), 0.0, (None,))
    stacked("blk.ln2.scale", (H,), 0.0, (None,))
    s.params["blk.ln2.scale"] += 1.0
    stacked("blk.ln2.bias", (H,), 0.0, (None,))
    am = math.sqrt(2.0 / (H + M))
    if cfg.n_experts:
        E = cfg.n_experts
        stacked("blk.router", (H, E), 0.02, ("embed", None))
        stacked("blk.w1", (E, H, M), am, ("expert", "embed", "mlp"))
        stacked("blk.w2", (E, M, H), am / math.sqrt(2 * L), ("expert", "mlp", "embed"))
    else:
        stacked("blk.w1", (H, M), am, ("embed", "mlp"))
        stacked("blk.b1", (M,), 0.0, ("mlp",))
        stacked("blk.w2", (M, H), am / math.sqrt(2 * L), ("mlp", "embed"))
        stacked("blk.b2", (H,), 0.0, (None,))
    s.layer_norm("ln_f", H)
    return s.params, s.axes


def _ln(x, scale, bias, eps=1e-5):
    from .common import raw_layer_norm

    return raw_layer_norm(x, scale, bias, eps)


# Layer scopes (`jax.named_scope`: HLO metadata, no op, no run-time cost).
# Every step function below is built from these helpers, so a profile's
# device time reduces by `embed`, `layers` and inside them `ln`, `qkv`,
# `kv_write`, `kv_gather` (serving/kv_cache.py), `attention`, `proj`,
# `mlp`, then `head`, whatever the shapes are (PERF.md section 3;
# tests/test_layer_scopes.py holds the list).


@jax.named_scope("qkv")
def _qkv(lp, y):
    qkv = y @ lp["blk.wqkv"].astype(y.dtype) + \
        lp["blk.bqkv"].astype(y.dtype)
    return jnp.split(qkv, 3, axis=-1)


@jax.named_scope("proj")
def _proj(lp, ctx, res=None):
    """Output projection, added to the residual stream `res` if given."""
    out = ctx @ lp["blk.wo"].astype(ctx.dtype)
    if res is not None:
        out = res + out
    return out + lp["blk.bo"].astype(ctx.dtype)


@jax.named_scope("head")
def _head(params: Params, x, prev_ids, eos_id: int):
    """Final LayerNorm, tied-embedding logits and the greedy pick for the
    rows `x` [N, H]; `prev_ids` [N] are the tokens that led to them."""
    x = _ln_named(params, "ln_f", x)
    logits = x @ params["wte.w"].T.astype(x.dtype)
    return _decoder.beam_top1(prev_ids.astype(jnp.int32), logits, eos_id)


def _attention(lp, x, cfg: GPTConfig, mesh=None):
    B, T, H = x.shape
    nh, hd = cfg.heads, cfg.head_dim
    q, k, v = _qkv(lp, x)
    q = q.reshape(B, T, nh, hd)
    k = k.reshape(B, T, nh, hd)
    v = v.reshape(B, T, nh, hd)
    from ..parallel.mesh import current_mesh
    from ..ops.pallas import attention as pa
    from ..ops.pallas import ring_attention as ra

    from ..parallel.sharding import in_manual_region

    mesh = mesh or current_mesh()
    # explicit ring attention over 'sp' — except inside an already-manual
    # region (the 'pp' pipeline): XLA cannot nest manual subregions, so
    # there GSPMD shards the sequence from the shard() constraints instead
    with jax.named_scope("attention"):
        if mesh is not None and mesh.shape.get("sp", 1) > 1 \
                and not in_manual_region():
            ctx = ra.ring_attention(q, k, v, mesh, axis="sp", causal=True)
        else:
            ctx = pa.mha(q, k, v, causal=True, scale=1.0 / math.sqrt(hd))
    return _proj(lp, ctx.reshape(B, T, H))


def _moe_mlp(lp, x, cfg: GPTConfig):
    """Switch-style top-1 routing with capacity (dispatch/combine einsums);
    expert weights sharded over 'ep'."""
    B, T, H = x.shape
    G = B * T
    E = cfg.n_experts
    C = max(1, int(cfg.capacity_factor * G / E))
    xt = x.reshape(G, H)
    logits = (xt @ lp["blk.router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate, idx = probs.max(-1), probs.argmax(-1)           # [G]
    eo = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # [G, E]
    pos = (jnp.cumsum(eo, axis=0) - 1.0) * eo             # position in expert
    within = (pos < C) * eo                               # keep under capacity
    po = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), C,
                        dtype=jnp.float32) * within.sum(-1, keepdims=True)
    dispatch = jnp.einsum("ge,gc->gec", within, po)       # [G, E, C]
    combine = dispatch * gate[:, None, None]
    ein = jnp.einsum("gec,gh->ech", dispatch.astype(x.dtype), xt)
    ein = shard(ein, ("expert", None, "embed"))
    h = gelu(jnp.einsum("ech,ehm->ecm", ein, lp["blk.w1"].astype(x.dtype)))
    h = shard(h, ("expert", None, "mlp"))
    out = jnp.einsum("ecm,emh->ech", h, lp["blk.w2"].astype(x.dtype))
    y = jnp.einsum("gec,ech->gh", combine.astype(x.dtype), out)
    return y.reshape(B, T, H)


def _block(lp, x, cfg: GPTConfig, mesh=None):
    """One transformer block with this layer's (unstacked) params."""
    h = _ln(x, lp["blk.ln1.scale"], lp["blk.ln1.bias"])
    x = x + _attention(lp, h, cfg, mesh)
    x = shard(x, ("batch", "seq", "embed"))
    h = _ln(x, lp["blk.ln2.scale"], lp["blk.ln2.bias"])
    with jax.named_scope("mlp"):
        if cfg.n_experts:
            x = x + _moe_mlp(lp, h, cfg)
        else:
            h = gelu(h @ lp["blk.w1"].astype(x.dtype)
                     + lp["blk.b1"].astype(x.dtype))
            h = shard(h, ("batch", "seq", "mlp"))
            x = x + (h @ lp["blk.w2"].astype(x.dtype)
                     + lp["blk.b2"].astype(x.dtype))
    return shard(x, ("batch", "seq", "embed"))


def _layer_params(params: Params):
    return {k: v for k, v in params.items() if k.startswith("blk.")}


def apply(params: Params, cfg: GPTConfig, ids: jax.Array,
          n_microbatches: int = 0) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab].

    n_microbatches > 0 runs the block stack through the GPipe pipeline over
    the 'pp' mesh axis (parallel/pipeline.py); 0 = lax.scan over layers.
    """
    from ..parallel.mesh import current_mesh

    B, T = ids.shape
    adt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = (params["wte.w"][ids] + params["wpe.w"][:T][None]).astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    lp_stacked = _layer_params(params)
    mesh = current_mesh()

    if n_microbatches and mesh is not None and mesh.shape.get("pp", 1) > 1:
        from ..parallel.pipeline import pipeline_apply

        S = mesh.shape["pp"]
        L = cfg.layers
        assert L % S == 0, f"layers {L} not divisible by pp {S}"
        # restack [L, ...] -> [S, L//S, ...]
        sp = jax.tree.map(
            lambda p: p.reshape((S, L // S) + p.shape[1:]), lp_stacked)
        assert B % n_microbatches == 0
        xm = x.reshape((n_microbatches, B // n_microbatches) + x.shape[1:])

        def stage_fn(stage_lp, xmb):
            def layer_body(h, lp):
                return _block(lp, h, cfg, mesh), None
            h, _ = jax.lax.scan(layer_body, xmb, stage_lp)
            return h

        x = pipeline_apply(stage_fn, sp, xm, mesh)
        x = x.reshape((B,) + x.shape[2:])
    else:
        def layer_body(h, lp):
            return _block(lp, h, cfg, mesh), None

        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(layer_body, x, lp_stacked)

    with jax.named_scope("head"):
        x = _ln_named(params, "ln_f", x)
        logits = x @ params["wte.w"].T.astype(x.dtype)
    return shard(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Decode path (serving/decode.py): paged-KV prefill + single-token steps.
#
# `apply` above recomputes the full [B, T] forward per call — fine for
# training/scoring, quadratic waste for token-by-token generation. The
# decode path splits generation into the two serving phases:
#
#   apply_prefill      one prompt ([1, T_bucket]) through full causal
#                      attention, writing every position's K/V into the
#                      sequence's pool blocks and sampling the first
#                      new token from the last real position;
#   apply_decode_step  one token per resident sequence ([S] slots),
#                      position-indexed attention over each sequence's
#                      own blocks via its block table — the executable
#                      every generated token after the first rides.
#
# Both take and return the pool arrays (donated at the jit boundary by
# the engine) and sample through ops/beam.beam_search with beam_size=1:
# greedy selection with the beam op's finished-freeze semantics, so a
# slot whose previous token is end_id keeps emitting end_id without any
# host-side branching.
#
# The programs themselves are `models/decoder.py`'s, shared with every
# model the engine serves; GPT-2 hands them its block's pieces as a
# `ServeModel` (`GPTConfig.serve_model()`), and the `apply_*` functions
# below are those programs with GPT-2's pieces. A Switch top-1 config
# (`n_experts > 0`) is refused there: `_moe_mlp` drops tokens over an
# expert's capacity, so a row's result depends on what shares its batch.
#
# Where the pools live. A pool is `[L, NB, BS, heads*head_dim]`
# (kv_cache.KVCacheConfig.pool_shape): the last dimension fills whole
# lanes, BS fills the sublanes, so the TPU stores it as written and a
# block is contiguous. All four programs run ONE layer loop,
# `decoder.serve_layers`, which holds `(h, k_pool, v_pool)` in the scan's
# CARRY (the stacked weights and the layer index are its `xs`): a layer
# writes `pool.at[l, blk, slot].set(kv)` and reads
# `pool[l, block_tables]` in the donated buffer itself. The pools must
# not be the scan's `xs`/`ys`: a scan's `ys` is a new stacked buffer, so
# every layer's slice is taken out, re-laid-out and written back and
# the donated pool copied whole, each step (three quarters of a decode
# step's device time when measured: PERF.md section 6, PR 25). How a
# token is stored is read from `pool.shape[3:]`, so a pool
# `[L, NB, BS, heads, head_dim]` is still served — slowly: the TPU pads
# and re-lays it out; the engine makes none.
# ---------------------------------------------------------------------------

@jax.named_scope("mlp")
def _decode_mlp(lp, x):
    h = gelu(x @ lp["blk.w1"].astype(x.dtype) + lp["blk.b1"].astype(x.dtype))
    return h @ lp["blk.w2"].astype(x.dtype) + lp["blk.b2"].astype(x.dtype)


class GPTServe(_decoder.ServeModel):
    """GPT-2's block for the serve programs: learned positions added at
    the embedding, LayerNorm, fused QKV with bias, dense GELU MLP, the
    tied embedding as the head."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.layers, self.heads = cfg.layers, cfg.heads
        self.head_dim = cfg.head_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len
        if cfg.n_experts:
            self.refusal = (
                "MoE decode is unsupported for models/gpt.py: its Switch "
                "top-1 router drops tokens over an expert's capacity, so "
                "a row's result depends on what shares its batch — serve "
                "a dense config, or a dropless model (models/olmoe.py)")

    def layer_params(self, params):
        return _layer_params(params)

    def embed(self, params, ids, positions):
        return params["wte.w"][ids] + params["wpe.w"][positions]

    def norm_attn(self, lp, h):
        return _ln(h, lp["blk.ln1.scale"], lp["blk.ln1.bias"])

    def qkv(self, lp, y, positions):
        return _qkv(lp, y)

    def proj(self, lp, ctx, res):
        return _proj(lp, ctx, res)

    def norm_mlp(self, lp, h):
        return _ln(h, lp["blk.ln2.scale"], lp["blk.ln2.bias"])

    def mlp(self, lp, y, params, l):
        return _decode_mlp(lp, y), None

    def head(self, params, x, prev_ids, eos_id):
        return _head(params, x, prev_ids, eos_id)


def apply_prefill(params: Params, cfg: GPTConfig, *args, **kw):
    """`decoder.prefill` with GPT-2's block."""
    return _decoder.prefill(cfg.serve_model(), params, *args, **kw)


def apply_decode_step(params: Params, cfg: GPTConfig, *args, **kw):
    """`decoder.decode_step` with GPT-2's block: (next tokens [S],
    k_pool, v_pool); a dense block has no counters to return."""
    return _decoder.decode_step(cfg.serve_model(), params, *args, **kw)[:3]


def apply_prefill_chunk(params: Params, cfg: GPTConfig, *args, **kw):
    """`decoder.prefill_chunk` with GPT-2's block."""
    return _decoder.prefill_chunk(cfg.serve_model(), params, *args, **kw)


def apply_verify_step(params: Params, cfg: GPTConfig, *args, **kw):
    """`decoder.verify_step` with GPT-2's block."""
    return _decoder.verify_step(cfg.serve_model(), params, *args, **kw)


def lm_loss(params: Params, cfg: GPTConfig, batch: Dict[str, jax.Array],
            rng=None, n_microbatches: int = 0) -> jax.Array:
    """Next-token cross entropy; batch = {"ids": [B, T+1]}."""
    ids = batch["ids"]
    logits = apply(params, cfg, ids[:, :-1], n_microbatches).astype(jnp.float32)
    targets = ids[:, 1:]
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return -ll.mean()


def make_batch(rng: jax.Array, cfg: GPTConfig, batch_size: int,
               seq_len: Optional[int] = None):
    T = seq_len or cfg.max_len
    return {"ids": jax.random.randint(rng, (batch_size, T + 1), 0,
                                      cfg.vocab_size)}
