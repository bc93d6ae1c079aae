"""The serve programs of a decoder-only model, and the interface the decode
engine drives (serving/decode.py).

`models/gpt.py` explains the two serving phases and where the KV pools
live; this module holds what is the same for every model: the ONE layer
loop with the pools in its carry (`serve_layers`) and the four programs
built on it (`prefill`, `decode_step`, `prefill_chunk`, `verify_step`).
What differs between GPT-2 (learned positions, LayerNorm, fused QKV with
bias, GELU MLP) and OLMoE (RoPE, RMSNorm, QK-norm, sparse SwiGLU experts)
is the block's pieces, which a model hands over as a `ServeModel`. The
engine asks a model configuration for it (`cfg.serve_model()`) and never
names a model module.

Attention here is multi-head over a pool `[L, NB, BS, heads*head_dim]`
(kv_cache.KVCacheConfig.pool_shape): both models have as many K/V heads as
query heads.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import Params


class ServeModel:
    """What the engine needs of a model: the cache's shape, the range of
    ids and positions, and the pieces of one block. `lp` is one layer's
    slice of `layer_params(params)`; activations are `[..., hidden]` with
    `positions` shaped like their leading dimensions.

    Every piece must be ROW-INDEPENDENT: a row's result may depend on that
    row alone, never on what shares its batch (the engine batches
    unrelated requests and promises each the tokens it would get alone). A
    model that cannot promise that says why in `refusal`."""

    layers: int
    heads: int
    head_dim: int
    vocab_size: int
    max_len: int            # positions the model can address
    refusal: Optional[str] = None

    @property
    def kv_heads(self) -> int:
        return self.heads

    def layer_params(self, params: Params) -> Params:
        """The per-layer parameters, stacked on a leading [L] axis."""
        raise NotImplementedError

    def embed(self, params: Params, ids, positions):
        raise NotImplementedError

    def norm_attn(self, lp, h):
        raise NotImplementedError

    def qkv(self, lp, y, positions):
        """(q, k, v), each `[..., heads*head_dim]`, as the cache stores
        and attention reads them (a rotary model rotates q and k here)."""
        raise NotImplementedError

    def proj(self, lp, ctx, res):
        """The output projection added to the residual stream `res`."""
        raise NotImplementedError

    def norm_mlp(self, lp, h):
        raise NotImplementedError

    def mlp(self, lp, y, params: Params, l):
        """(the block's second half for `y`, a small pytree of per-layer
        counters or None). The counters of a decode step's layers come
        back stacked from `decode_step` and `step_facts` names them.
        `params` are the model's whole parameters and `l` this layer's
        index, for a piece that must address a stacked tensor in place
        (a kernel's operand cannot be a slice without being a copy):
        such a tensor is then left out of `layer_params`."""
        raise NotImplementedError

    def head(self, params: Params, x, prev_ids, eos_id: int):
        """Greedy next tokens [N] for the rows `x` [N, hidden]."""
        raise NotImplementedError

    def step_facts(self, stats) -> Dict:
        """Fields for a decode step's record (`decode.steps`) from the
        step's stacked counters, fetched to the host."""
        return {}


def beam_top1(prev_ids: jax.Array, logits: jax.Array,
              eos_id: int) -> jax.Array:
    """Greedy next-token selection through the beam_search op (K=1).
    prev_ids [S] int32, logits [S, vocab] → [S] int32."""
    from ..ops.beam import beam_search

    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    out = beam_search(
        {"pre_ids": [prev_ids[:, None].astype(jnp.int32)],
         "pre_scores": [jnp.zeros((logp.shape[0], 1), jnp.float32)],
         "scores": [logp[:, None, :]]},
        {"beam_size": 1, "end_id": int(eos_id), "is_accumulated": True},
        None)
    return out["selected_ids"][:, 0].astype(jnp.int32)


def serve_layers(model: ServeModel, params: Params, x: jax.Array,
                 positions: jax.Array, k_pool: jax.Array,
                 v_pool: jax.Array, attend):
    """The serve programs' layer loop: `x` through every block with the
    pools in the loop's carry. `attend(l, q, k, v, kp, vp)` is the one
    part the programs differ in: it gets the layer index, the layer's
    projections (x's leading shape, `[..., heads*head_dim]` each) and the
    WHOLE pools, writes k/v at (l, block, slot), and returns
    `(ctx [..., heads*head_dim], kp, vp)`. Returns (x, k_pool, v_pool,
    the layers' stacked counters or None)."""

    def layer_body(carry, per_layer):
        h, kp, vp = carry
        lp, l = per_layer
        y = model.norm_attn(lp, h)
        q, k, v = model.qkv(lp, y, positions)
        ctx, kp, vp = attend(l, q, k, v, kp, vp)
        h = model.proj(lp, ctx, h)
        y = model.norm_mlp(lp, h)
        out, stats = model.mlp(lp, y, params, l)
        return (h + out, kp, vp), stats

    layers = jnp.arange(k_pool.shape[0], dtype=jnp.int32)
    with jax.named_scope("layers"):
        (x, k_pool, v_pool), stats = jax.lax.scan(
            layer_body, (x, k_pool, v_pool),
            (model.layer_params(params), layers))
    return x, k_pool, v_pool, stats


def prefill(model: ServeModel, params: Params, ids: jax.Array,
            length: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
            block_table: jax.Array, *, block_size: int,
            eos_id: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One prompt through the stack, filling its KV blocks.

    ids [1, T] (edge-padded to the prefill bucket T), length = true
    prompt length, block_table [MB] (the sequence's row). Returns
    (first sampled token [1], k_pool, v_pool). Padded tail positions
    write to the null block / soon-overwritten slots (see
    kv_cache.write_prefill_kv) and, being causally AFTER every real
    position, never contribute to the last real position's logits.
    """
    from ..ops.pallas import attention as pa
    from ..serving import kv_cache as kvc

    B, T = ids.shape
    nh, hd = model.heads, model.head_dim
    adt = k_pool.dtype
    stored = k_pool.shape[3:]     # how the pool stores one token
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    with jax.named_scope("embed"):
        x = model.embed(params, ids, positions).astype(adt)

    def attend(l, q, k, v, kp, vp):
        kp = kvc.write_prefill_kv(kp, l, k[0].reshape(T, *stored),
                                  block_table, block_size)
        vp = kvc.write_prefill_kv(vp, l, v[0].reshape(T, *stored),
                                  block_table, block_size)
        q = q.reshape(B, T, nh, hd)
        k = k.reshape(B, T, nh, hd)
        v = v.reshape(B, T, nh, hd)
        with jax.named_scope("attention"):
            ctx = pa.mha(q, k, v, causal=True, scale=1.0 / math.sqrt(hd))
        return ctx.reshape(B, T, nh * hd), kp, vp

    x, k_pool, v_pool, _ = serve_layers(model, params, x, positions,
                                        k_pool, v_pool, attend)
    # the final norm is per row: the last real position alone goes through
    last = jnp.maximum(length, 1) - 1
    tok = model.head(params, x[0, last][None], ids[0, last][None], eos_id)
    return tok, k_pool, v_pool


def gather_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     layer: jax.Array, block_tables: jax.Array,
                     positions: jax.Array, heads: int) -> jax.Array:
    """Decode attention over a gathered copy of every slot's whole table:
    q `[S, heads*head_dim]` against layer `layer` of the pools through
    block_tables `[S, MB]`, key positions `<= positions[s]` -> `[S,
    heads*head_dim]`. The route off the TPU, and what the paged kernel
    (ops/pallas/paged_attention.py) is compared with on it."""
    from ..serving import kv_cache as kvc

    S = q.shape[0]
    hd = q.shape[1] // heads
    keys = kvc.gather_kv(k_pool, layer, block_tables)   # [S, M, *stored]
    vals = kvc.gather_kv(v_pool, layer, block_tables)
    m = keys.shape[1]
    q = q.reshape(S, heads, hd)
    keys = keys.reshape(S, m, heads, hd)
    vals = vals.reshape(S, m, heads, hd)
    with jax.named_scope("attention"):
        scores = jnp.einsum("snd,smnd->snm", q, keys) * (1.0 / math.sqrt(hd))
        mask = jnp.arange(m, dtype=jnp.int32)[None, :] \
            <= positions[:, None]
        scores = jnp.where(mask[:, None, :], scores, -1e9)
        att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        ctx = jnp.einsum("snm,smnd->snd", att.astype(k_pool.dtype), vals)
    return ctx.reshape(S, heads * hd)


def decode_step(model: ServeModel, params: Params, ids: jax.Array,
                positions: jax.Array, k_pool: jax.Array,
                v_pool: jax.Array, block_tables: jax.Array, *,
                block_size: int, eos_id: int):
    """One decode step for S resident slots.

    ids [S] (each slot's previous token), positions [S] (where this
    token's K/V lands = current sequence length), block_tables [S, MB].
    Every row's math touches only that row's activations and its own
    blocks, so a slot's tokens are bit-identical whatever else shares
    the batch — the property test_decode's admit-mid-decode test pins.
    Returns (next tokens [S], k_pool, v_pool, the layers' stacked
    counters or None: `ServeModel.mlp`)."""
    from ..ops.pallas import paged_attention as pa
    from ..serving import kv_cache as kvc

    S = ids.shape[0]
    nh = model.heads
    adt = k_pool.dtype
    stored = k_pool.shape[3:]     # how the pool stores one token
    with jax.named_scope("embed"):
        x = model.embed(params, ids, positions).astype(adt)

    # the one gate (ops/pallas/paged_attention.py): on a TPU the kernel
    # reads the live blocks through the table; elsewhere the gather below
    paged = pa.use_paged(x, k_pool, nh)
    pa.GATE_COUNTS["paged" if paged else "gather"] += 1

    def attend(l, q, k, v, kp, vp):
        kp = kvc.write_token_kv(kp, l, k.reshape(S, *stored), block_tables,
                                positions, block_size)
        vp = kvc.write_token_kv(vp, l, v.reshape(S, *stored), block_tables,
                                positions, block_size)
        if paged:
            with jax.named_scope("attention"):
                ctx = pa.paged_attention(q, kp, vp, l, block_tables,
                                         positions, heads=nh)
        else:
            ctx = gather_attention(q, kp, vp, l, block_tables, positions, nh)
        return ctx, kp, vp

    x, k_pool, v_pool, stats = serve_layers(model, params, x, positions,
                                            k_pool, v_pool, attend)
    return model.head(params, x, ids, eos_id), k_pool, v_pool, stats


def prefill_chunk(model: ServeModel, params: Params, ids: jax.Array,
                  start: jax.Array, length: jax.Array,
                  k_pool: jax.Array, v_pool: jax.Array,
                  block_table: jax.Array, *, block_size: int,
                  eos_id: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fixed-size SLICE of a prompt through the stack (chunked
    prefill — serving/kv_reuse.py).

    ids [1, C] = the tokens at positions start..start+C-1 (edge-padded
    past `length`), start = the slice's first position, length = the
    true prompt length. Writes the slice's K/V into the sequence's
    blocks and attends gather-style over the block table with mask
    `key_pos <= start + i`, so earlier slices' — and prefix-cache
    reused blocks' — K/V participate exactly as in a whole-prompt
    prefill. Per-position results are independent of where the chunk
    boundaries fall (each row's math reads only pool state + its own
    activations), which is what makes chunked == whole prefill and
    reused == recomputed prefixes hold at the token level. Returns
    (tok [1], k_pool, v_pool); tok is meaningful only on the slice
    containing position length-1 (the scheduler ignores it earlier).
    """
    from ..serving import kv_cache as kvc

    _, C = ids.shape
    nh, hd = model.heads, model.head_dim
    adt = k_pool.dtype
    stored = k_pool.shape[3:]     # how the pool stores one token
    pos = start + jnp.arange(C, dtype=jnp.int32)
    # the final slice's padded tail can run past the positions the model
    # addresses; clamp (those rows' outputs are never consumed, their KV
    # lands in the null block / overwritten slots)
    with jax.named_scope("embed"):
        x = model.embed(params, ids[0],
                        jnp.minimum(pos, model.max_len - 1)).astype(adt)

    scale = 1.0 / math.sqrt(hd)

    def attend(l, q, k, v, kp, vp):
        kp = kvc.write_chunk_kv(kp, l, k.reshape(C, *stored), block_table,
                                start, block_size)
        vp = kvc.write_chunk_kv(vp, l, v.reshape(C, *stored), block_table,
                                start, block_size)
        keys = kvc.gather_kv(kp, l, block_table[None])[0]   # [M, *stored]
        vals = kvc.gather_kv(vp, l, block_table[None])[0]
        m = keys.shape[0]
        q = q.reshape(C, nh, hd)
        keys = keys.reshape(m, nh, hd)
        vals = vals.reshape(m, nh, hd)
        with jax.named_scope("attention"):
            scores = jnp.einsum("cnd,mnd->cnm", q, keys) * scale
            mask = jnp.arange(m, dtype=jnp.int32)[None, :] <= pos[:, None]
            scores = jnp.where(mask[:, None, :], scores, -1e9)
            att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            ctx = jnp.einsum("cnm,mnd->cnd", att.astype(adt), vals)
        return ctx.reshape(C, nh * hd), kp, vp

    x, k_pool, v_pool, _ = serve_layers(model, params, x, pos, k_pool,
                                        v_pool, attend)
    last = jnp.clip(length - 1 - start, 0, C - 1)
    tok = model.head(params, x[last][None], ids[0, last][None], eos_id)
    return tok, k_pool, v_pool


def verify_step(model: ServeModel, params: Params, ids: jax.Array,
                positions: jax.Array, k_pool: jax.Array,
                v_pool: jax.Array, block_tables: jax.Array, *,
                block_size: int, eos_id: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative verification: W = k+1 tokens per slot in ONE step
    (serving/kv_reuse.py).

    ids [S, W] = each slot's [last_token, d_1..d_k] (the previous real
    token followed by the draft model's k proposals), positions [S] =
    each slot's next KV write position. Row j writes its K/V at
    position positions+j and attends `key_pos <= positions + j`, so
    output j is bit-identical to the token a plain decode_step
    sequence would produce after feeding ids[:, :j+1] one at a time —
    the exact greedy accept/reject in kv_reuse.accept_length compares
    drafts against these outputs. Rejected positions' K/V stays in the
    pool but is overwritten by the next real write before any mask
    lets it be read (the standard paged-decode invariant). Sampling
    routes through the same beam_search op as decode, so an eos in the
    fed window freezes the remaining outputs to eos. Returns
    (tokens [S, W], k_pool, v_pool)."""
    from ..serving import kv_cache as kvc

    S, W = ids.shape
    nh, hd = model.heads, model.head_dim
    adt = k_pool.dtype
    stored = k_pool.shape[3:]     # how the pool stores one token
    pos = positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    with jax.named_scope("embed"):
        x = model.embed(params, ids,
                        jnp.minimum(pos, model.max_len - 1)).astype(adt)

    scale = 1.0 / math.sqrt(hd)

    def attend(l, q, k, v, kp, vp):
        kp = kvc.write_span_kv(kp, l, k.reshape(S, W, *stored), block_tables,
                               positions, block_size)
        vp = kvc.write_span_kv(vp, l, v.reshape(S, W, *stored), block_tables,
                               positions, block_size)
        keys = kvc.gather_kv(kp, l, block_tables)       # [S, M, *stored]
        vals = kvc.gather_kv(vp, l, block_tables)
        m = keys.shape[1]
        q = q.reshape(S, W, nh, hd)
        keys = keys.reshape(S, m, nh, hd)
        vals = vals.reshape(S, m, nh, hd)
        with jax.named_scope("attention"):
            scores = jnp.einsum("swnd,smnd->swnm", q, keys) * scale
            mask = jnp.arange(m, dtype=jnp.int32)[None, None, :] \
                <= pos[:, :, None]
            scores = jnp.where(mask[:, :, None, :], scores, -1e9)
            att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            ctx = jnp.einsum("swnm,smnd->swnd", att.astype(adt), vals)
        return ctx.reshape(S, W, nh * hd), kp, vp

    x, k_pool, v_pool, _ = serve_layers(model, params, x, pos, k_pool,
                                        v_pool, attend)
    tokens = model.head(params, x.reshape(S * W, -1), ids.reshape(S * W),
                        eos_id).reshape(S, W)
    return tokens, k_pool, v_pool
