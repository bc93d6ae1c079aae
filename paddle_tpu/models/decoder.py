"""The serve programs of a decoder-only model, and the interface the decode
engine drives (serving/decode.py).

`models/gpt.py` explains the two serving phases and where the KV pools
live; this module holds what is the same for every model: the ONE layer
loop with the pools in its carry (`serve_layers`) and the four programs
built on it (`prefill`, `decode_step`, `prefill_chunk`, `verify_step`).
What differs between models (learned positions or RoPE, LayerNorm or
RMSNorm, a dense MLP or sparse experts, leading layers unlike the rest,
multi-head or latent attention) is the block's pieces, which a model
hands over as a `ServeModel`. The engine asks a model configuration for
it (`cfg.serve_model()`) and never names a model module.

The cache belongs to the model too. A token stores two entries in a
layer, one in each pool (`kv_cache.KVCacheConfig`): `ServeModel.stored`
says how wide they are, `qkv` produces them, the programs write them at
(layer, block, slot) and hand them back gathered, or leave them in the
pools for a kernel, and the model's three attention forms read them:
`attend_prompt` (a whole prompt from its own projections),
`attend_cached` (query rows against a gathered context) and
`attend_paged` (a decode step through the block table, on a TPU). The
defaults are multi-head attention over K and V of `heads*head_dim`
lanes, as many K/V heads as query heads.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .common import Params


class ServeModel:
    """What the engine needs of a model: the cache's shape, the range of
    ids and positions, and the pieces of one block. `lp` is one layer's
    parameters (a slice of `layer_params(params)`, or one of
    `lead_params(params)`); activations are `[..., hidden]` with
    `positions` shaped like their leading dimensions.

    Every piece must be ROW-INDEPENDENT: a row's result may depend on that
    row alone, never on what shares its batch (the engine batches
    unrelated requests and promises each the tokens it would get alone). A
    model that cannot promise that says why in `refusal`."""

    layers: int             # all of them, leading ones included
    heads: int
    head_dim: int
    vocab_size: int
    max_len: int            # positions the model can address
    refusal: Optional[str] = None

    @property
    def kv_heads(self) -> int:
        return self.heads

    @property
    def stored(self) -> Tuple[int, int]:
        """Lanes of the two entries a token stores in a layer; multi-head
        attention's are K and V, `kv_heads*head_dim` each."""
        return (self.kv_heads * self.head_dim,) * 2

    def lead_params(self, params: Params) -> Sequence[Params]:
        """The parameters of the layers BEFORE the stacked ones, one dict a
        layer, where the first layers are unlike the rest (a dense MLP
        before expert layers); the layer loop runs them one by one and
        then scans the stack."""
        return ()

    def layer_params(self, params: Params) -> Params:
        """The parameters of the layers that are alike, stacked on a
        leading axis."""
        raise NotImplementedError

    def embed(self, params: Params, ids, positions):
        raise NotImplementedError

    def norm_attn(self, lp, h):
        raise NotImplementedError

    def qkv(self, lp, y, positions):
        """(q, k, v): `k` and `v` `[..., width]` are the two entries the
        cache stores for each row, as attention reads them back (a rotary
        model rotates here); `q` is whatever the model's own `attend_*`
        take, `[..., heads*head_dim]` for the defaults."""
        raise NotImplementedError

    def attend_prompt(self, lp, q, k, v):
        """Causal self-attention of whole prompts `[B, T, ...]` from the
        layer's own projections (what `qkv` gave for these rows) ->
        `[B, T, ctx]`, what `proj` takes."""
        from ..ops.pallas import attention as pa

        B, T = q.shape[:2]
        heads = (B, T, self.heads, self.head_dim)
        ctx = pa.mha(q.reshape(heads), k.reshape(heads), v.reshape(heads),
                     causal=True, scale=1.0 / math.sqrt(self.head_dim))
        return ctx.reshape(B, T, -1)

    def attend_cached(self, lp, q, keys, vals, pos):
        """Query rows against a gathered context: q `[S, W, ...]`, the
        stored entries `keys` `[S, M, k width]` and `vals` `[S, M, v
        width]` of positions 0..M-1, `pos` `[S, W]`: row (s, w) sees the
        positions `<= pos[s, w]` -> `[S, W, ctx]`."""
        return mha_cached(q, keys, vals, pos, self.heads)

    def paged_route(self, x, k_pool, v_pool) -> Optional[str]:
        """The name (a key of `paged_attention.GATE_COUNTS`) of the kernel
        a decode step over these pools takes, or None: the gathered form."""
        from ..ops.pallas import paged_attention as pa

        return "paged" if pa.use_paged(x, k_pool, self.heads) else None

    def attend_paged(self, lp, q, k_pool, v_pool, layer, block_tables,
                     positions):
        """One query row a slot, q `[S, ...]`, against the live blocks of
        layer `layer` of the pools through the table -> `[S, ctx]`; only
        where `paged_route` named a kernel."""
        from ..ops.pallas import paged_attention as pa

        return pa.paged_attention(q, k_pool, v_pool, layer, block_tables,
                                  positions, heads=self.heads)

    def proj(self, lp, ctx, res):
        """The output projection added to the residual stream `res`."""
        raise NotImplementedError

    def norm_mlp(self, lp, h):
        raise NotImplementedError

    def mlp(self, lp, y, params: Params, l):
        """(the block's second half for `y`, a small pytree of per-layer
        counters or None). The counters of a decode step's stacked layers
        come back stacked from `decode_step` and `step_facts` names them.
        `params` are the model's whole parameters and `l` this layer's
        index among ALL layers, for a piece that must address a stacked
        tensor in place (a kernel's operand cannot be a slice without
        being a copy): such a tensor is then left out of `layer_params`.
        A leading layer's `lp` says by its keys what kind it is."""
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


@jax.named_scope("head")
def rms_head(params: Params, x: jax.Array, prev_ids: jax.Array, eos_id: int,
             eps: float) -> jax.Array:
    """Final RMSNorm (`ln_f.scale`), an untied output head (`head.w`) and
    the greedy pick for the rows `x` [N, H]; `prev_ids` [N] are the tokens
    that led to them."""
    from .common import rms_norm

    x = rms_norm(x, params["ln_f.scale"], eps)
    # float32 logits: bf16 ones lie 0.03 apart near the top of a row, and
    # the greedy pick would be made among ties
    logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                     preferred_element_type=jnp.float32)
    return beam_top1(prev_ids.astype(jnp.int32), logits, eos_id)


def serve_layers(model: ServeModel, params: Params, x: jax.Array,
                 positions: jax.Array, k_pool: jax.Array,
                 v_pool: jax.Array, attend):
    """The serve programs' layer loop: `x` through every block with the
    pools in the loop's carry: the model's leading layers one by one, then
    a scan over the stacked ones (a model whose layers are all alike has
    no leading ones, and the loop is the scan). `attend(l, lp, q, k, v,
    kp, vp)` is the one part the programs differ in: it gets the layer
    index, the layer's parameters and projections and the WHOLE pools,
    writes k/v at (l, block, slot), and returns `(ctx, kp, vp)`. Returns
    (x, k_pool, v_pool, the stacked layers' counters or None)."""

    def layer_body(carry, per_layer):
        h, kp, vp = carry
        lp, l = per_layer
        y = model.norm_attn(lp, h)
        q, k, v = model.qkv(lp, y, positions)
        ctx, kp, vp = attend(l, lp, q, k, v, kp, vp)
        h = model.proj(lp, ctx, h)
        y = model.norm_mlp(lp, h)
        out, stats = model.mlp(lp, y, params, l)
        return (h + out, kp, vp), stats

    lead = model.lead_params(params)
    layers = jnp.arange(len(lead), k_pool.shape[0], dtype=jnp.int32)
    with jax.named_scope("layers"):
        carry = (x, k_pool, v_pool)
        for l, lp in enumerate(lead):
            carry, _ = layer_body(carry, (lp, jnp.int32(l)))
        (x, k_pool, v_pool), stats = jax.lax.scan(
            layer_body, carry, (model.layer_params(params), layers))
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
    from ..serving import kv_cache as kvc

    B, T = ids.shape
    adt = k_pool.dtype
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    with jax.named_scope("embed"):
        x = model.embed(params, ids, positions).astype(adt)

    def attend(l, lp, q, k, v, kp, vp):
        kp = kvc.write_prefill_kv(kp, l, k[0].reshape(T, *kp.shape[3:]),
                                  block_table, block_size)
        vp = kvc.write_prefill_kv(vp, l, v[0].reshape(T, *vp.shape[3:]),
                                  block_table, block_size)
        with jax.named_scope("attention"):
            ctx = model.attend_prompt(lp, q, k, v)
        return ctx, kp, vp

    x, k_pool, v_pool, _ = serve_layers(model, params, x, positions,
                                        k_pool, v_pool, attend)
    # the final norm is per row: the last real position alone goes through
    last = jnp.maximum(length, 1) - 1
    tok = model.head(params, x[0, last][None], ids[0, last][None], eos_id)
    return tok, k_pool, v_pool


def mha_cached(q: jax.Array, keys: jax.Array, vals: jax.Array,
               pos: jax.Array, heads: int) -> jax.Array:
    """Multi-head attention of query rows over a gathered context (the
    default `ServeModel.attend_cached`): q `[S, W, heads*head_dim]`, keys
    and vals `[S, M, heads*head_dim]`, row (s, w) sees key positions `<=
    pos[s, w]` -> `[S, W, heads*head_dim]`."""
    S, W, width = q.shape
    m = keys.shape[1]
    hd = width // heads
    q = q.reshape(S, W, heads, hd)
    keys = keys.reshape(S, m, heads, hd)
    vals = vals.reshape(S, m, heads, hd)
    scores = jnp.einsum("swnd,smnd->swnm", q, keys) * (1.0 / math.sqrt(hd))
    mask = jnp.arange(m, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
    scores = jnp.where(mask[:, :, None, :], scores, -1e9)
    att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    ctx = jnp.einsum("swnm,smnd->swnd", att.astype(keys.dtype), vals)
    return ctx.reshape(S, W, width)


def cached_attention(attend, q: jax.Array, k_pool: jax.Array,
                     v_pool: jax.Array, layer: jax.Array,
                     block_tables: jax.Array, pos: jax.Array) -> jax.Array:
    """Attention of the query rows q `[S, W, ...]` over a gathered copy of
    every slot's whole table: layer `layer` of the pools through
    block_tables `[S, MB]`, key positions `<= pos[s, w]`, by `attend(q,
    keys, vals, pos)`, a model's `attend_cached` with its layer's
    parameters bound -> `[S, W, ctx]`. What `prefill_chunk` and
    `verify_step` run everywhere and `decode_step` off the TPU, and what a
    paged kernel is compared with on it."""
    from ..serving import kv_cache as kvc

    keys = kvc.gather_kv(k_pool, layer, block_tables)   # [S, M, width]
    vals = kvc.gather_kv(v_pool, layer, block_tables)
    with jax.named_scope("attention"):
        return attend(q, keys, vals, pos)


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
    adt = k_pool.dtype
    with jax.named_scope("embed"):
        x = model.embed(params, ids, positions).astype(adt)

    # the one gate (ops/pallas/paged_attention.py, asked through the model,
    # whose cache it is): on a TPU a kernel reads the live blocks through
    # the table; elsewhere the gathered form below
    route = model.paged_route(x, k_pool, v_pool)
    pa.GATE_COUNTS[route or "gather"] += 1

    def attend(l, lp, q, k, v, kp, vp):
        kp = kvc.write_token_kv(kp, l, k.reshape(S, *kp.shape[3:]),
                                block_tables, positions, block_size)
        vp = kvc.write_token_kv(vp, l, v.reshape(S, *vp.shape[3:]),
                                block_tables, positions, block_size)
        if route:
            with jax.named_scope("attention"):
                ctx = model.attend_paged(lp, q, kp, vp, l, block_tables,
                                         positions)
        else:
            ctx = cached_attention(
                functools.partial(model.attend_cached, lp), q[:, None], kp,
                vp, l, block_tables, positions[:, None])[:, 0]
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
    adt = k_pool.dtype
    pos = start + jnp.arange(C, dtype=jnp.int32)
    # the final slice's padded tail can run past the positions the model
    # addresses; clamp (those rows' outputs are never consumed, their KV
    # lands in the null block / overwritten slots)
    with jax.named_scope("embed"):
        x = model.embed(params, ids[0],
                        jnp.minimum(pos, model.max_len - 1)).astype(adt)

    def attend(l, lp, q, k, v, kp, vp):
        kp = kvc.write_chunk_kv(kp, l, k.reshape(C, *kp.shape[3:]),
                                block_table, start, block_size)
        vp = kvc.write_chunk_kv(vp, l, v.reshape(C, *vp.shape[3:]),
                                block_table, start, block_size)
        # one "slot" of C query rows over the sequence's own table
        ctx = cached_attention(
            functools.partial(model.attend_cached, lp), q[None], kp, vp, l,
            block_table[None], pos[None])[0]
        return ctx, kp, vp

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
    adt = k_pool.dtype
    pos = positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    with jax.named_scope("embed"):
        x = model.embed(params, ids,
                        jnp.minimum(pos, model.max_len - 1)).astype(adt)

    def attend(l, lp, q, k, v, kp, vp):
        kp = kvc.write_span_kv(kp, l, k.reshape(S, W, *kp.shape[3:]),
                               block_tables, positions, block_size)
        vp = kvc.write_span_kv(vp, l, v.reshape(S, W, *vp.shape[3:]),
                               block_tables, positions, block_size)
        ctx = cached_attention(functools.partial(model.attend_cached, lp),
                               q, kp, vp, l, block_tables, pos)
        return ctx, kp, vp

    x, k_pool, v_pool, _ = serve_layers(model, params, x, pos, k_pool,
                                        v_pool, attend)
    tokens = model.head(params, x.reshape(S * W, -1), ids.reshape(S * W),
                        eos_id).reshape(S, W)
    return tokens, k_pool, v_pool
