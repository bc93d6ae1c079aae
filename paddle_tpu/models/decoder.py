"""The serve programs of a decoder-only model, and the interface the decode
engine drives (serving/decode.py).

`models/gpt.py` explains the two serving phases and where the KV pools
live; this module holds what is the same for every model: the ONE layer
loop with the pools in its carry (`serve_layers`) and the four programs
built on it (`prefill`, `decode_step`, `prefill_chunk`, `verify_step`).
What differs between models (learned positions or RoPE, LayerNorm or
RMSNorm, a dense MLP or sparse experts, leading layers unlike the rest,
multi-head, grouped-query or latent attention; or blocks of ONE mixer each
in a pattern, some of them recurrent) is the block's pieces, which a model
hands over as a `ServeModel`. The engine asks a model configuration for
it (`cfg.serve_model()`) and never names a model module.

The cache belongs to the model too. A token stores two entries in a
layer, one in each pool (`kv_cache.KVCacheConfig`): `ServeModel.stored`
says how wide they are, `qkv` produces them, the programs write them at
(layer, block, slot) and hand them back gathered, or leave them in the
pools for a kernel, and the model's three attention forms read them:
`attend_prompt` (a whole prompt from its own projections),
`attend_cached` (query rows against a gathered context) and
`attend_paged` (a decode step through the block table, on a TPU). A model
may store a THIRD entry at a rate, one every so many tokens
(`ServeModel.rated`: compressed keys a block-sparse attention scores
before it reads any K/V); its pools ride beside the state pools in the
programs' `state` and the programs hand them to the attention pieces. The
defaults are multi-head attention over K and V of `kv_heads*head_dim`
lanes; with fewer K/V heads than query heads the `heads / kv_heads` query
heads of a K/V head read its lanes together (grouped-query attention).

A model may also keep STATE that is not a token's: a recurrent layer's
fixed-size state a sequence (`ServeModel.state_pools`: row pools `[layers
of the kind, rows, ...]`, row 0 the null row idle slots read and write).
The engine owns the pools and hands the programs a row id a sequence, as
it hands them block tables; the programs carry the pools beside the K/V
pools and the MODEL reads and writes its rows (`ssm_prompt`, `ssm_token`).

A model may keep TWO KINDS of cache (`ServeModel.window`): layers that see
the newest `window` keys alone (`W` blocks of a `pattern`) among layers that
see every key (`*`). The window layers' K/V live in pools of their own,
`[window_layers, NBw, BS, width]`, under tables of their own
(`kv_cache.window_table`: a sequence's ring of blocks, repeated), which the
engine hands the programs beside `state`; the pools ride LAST in `state`.
Every writer and reader addresses position `p` at block `p // BS` of the
kind's table as ever; what is new is that a window layer's readers start at
the window's first key (`gqa_slice`, `mha_cached`, the paged walk's
`window_tables`).

A model whose prompts are too long for one pass says so with
`prompt_slice`: its prefill program walks the prompt in slices of that
many tokens (`prefill_sliced`), each slice through every block, its K/V
written before its queries read the cache so far, the recurrent state
carried from slice to slice in its row: no temporary grows with the
prompt.
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
    # blocks of ONE mixer each: a character a block, `M` a recurrent (state
    # space) mixer, `E` the `mlp` piece alone, `*` attention alone, `W`
    # attention over the newest `window` keys alone; None: every block is
    # attention then `mlp` (`serve_layers` says how each runs)
    pattern: Optional[str] = None
    # keys a `W` block's query sees, its own among them; None: the model has
    # no such block and ONE kind of cache
    window: Optional[int] = None
    # tokens a slice of the prefill program's walk over a prompt
    # (`prefill_sliced`); None: a prompt goes through in one pass
    prompt_slice: Optional[int] = None
    # attention-then-`mlp` SUB-BLOCKS a layer, each with a cache layer of
    # its own (`serve_layers`, `sub_params`): a layer of two attention
    # sub-layers stores `2 x layers` layers of K/V
    sub_blocks: int = 1
    # the whole-prompt prefill program returns the layers' counters too
    # (`prefill`: beside the pools, as `decode_step` returns them), for a
    # model whose prompts' routing a step record should show; False: it
    # returns what it always has
    prefill_counters: bool = False

    @property
    def rated(self) -> Tuple[Tuple[int, int], ...]:
        """Entries stored at a RATE beside the two a token: (lanes, stride
        in tokens) each, one entry every `stride` tokens a layer of the
        K/V pools, in pools of their own that follow the block table
        (`kv_cache.KVCacheConfig.rated`); the programs carry them after the
        state pools in `state`. () for a model that stores none."""
        return ()

    @property
    def kv_heads(self) -> int:
        return self.heads

    @property
    def kv_layers(self) -> int:
        """Layers of the K/V pools: one for every attention sub-layer."""
        return self.layers * self.sub_blocks

    @property
    def window_layers(self) -> int:
        """Layers of the WINDOW kind's pools (the `W` blocks); 0 for a model
        of one cache kind."""
        return (self.pattern or "").count("W") if self.window else 0

    def state_pools(self, rows: int, dtype) -> Tuple:
        """((shape, dtype), ...) of the pools of per-sequence state that is
        not K/V, `[layers of the kind, rows, ...]` each, for `rows` rows
        (row 0 the null row) at the served `dtype`; () for a model whose
        sequences keep nothing but their blocks."""
        return ()

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

    def sub_params(self, lp: Params, j: int) -> Params:
        """Sub-block `j`'s parameters out of one layer's `lp`, where a
        layer holds several (`sub_blocks`); what every piece of that
        sub-block is handed as its `lp`."""
        return lp

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

        if self.kv_heads != self.heads:
            return gqa_prompt(q, k, v, self.heads, self.kv_heads)
        B, T = q.shape[:2]
        heads = (B, T, self.heads, self.head_dim)
        ctx = pa.mha(q.reshape(heads), k.reshape(heads), v.reshape(heads),
                     causal=True, scale=1.0 / math.sqrt(self.head_dim))
        return ctx.reshape(B, T, -1)

    def attend_cached(self, lp, q, keys, vals, pos, extra=()):
        """Query rows against a gathered context: q `[S, W, ...]`, the
        stored entries `keys` `[S, M, k width]` and `vals` `[S, M, v
        width]` of positions 0..M-1, `pos` `[S, W]`: row (s, w) sees the
        positions `<= pos[s, w]` -> `[S, W, ctx]`. `extra`: the rated
        entries gathered through the same tables, `[S, MB, E * width]` each
        (`kv_cache.gather_rated`), for a model that stores them. A model
        with `W` blocks also takes `window=` (a `W` block alone is told it:
        the newest `window` of those positions alone, `mha_cached`)."""
        return mha_cached(q, keys, vals, pos, self.heads, self.kv_heads)

    def paged_route(self, x, k_pool, v_pool) -> Optional[str]:
        """The name (a key of `paged_attention.GATE_COUNTS`) of the kernel
        a decode step over these pools takes, or None: the gathered form."""
        from ..ops.pallas import paged_attention as pa

        if self.kv_heads != self.heads:
            return "paged_gqa" if pa.use_paged_gqa(
                x, k_pool, self.heads, self.kv_heads) else None
        return "paged" if pa.use_paged(x, k_pool, self.heads) else None

    def attend_paged(self, lp, q, k_pool, v_pool, layer, block_tables,
                     positions, rated=()):
        """One query row a slot, q `[S, ...]`, against the live blocks of
        layer `layer` of the pools through the table -> `[S, ctx]`; only
        where `paged_route` named a kernel. `rated`: the pools of the
        entries stored at a rate, for a model that stores them. A model
        with `W` blocks also takes `window=` (a `W` block alone is told it:
        the pools and tables are then the window kind's, the tables as
        `paged_attention.window_tables` made them)."""
        from ..ops.pallas import paged_attention as pa

        if self.kv_heads != self.heads:
            return pa.paged_gqa_attention(
                q, k_pool, v_pool, layer, block_tables, positions,
                heads=self.heads, kv_heads=self.kv_heads)
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

    # -- a model of one mixer a block (`pattern`) -------------------------

    def block_params(self, params: Params, kind: str, i: int) -> Params:
        """The parameters of block `i` AMONG THE BLOCKS OF ITS KIND (a
        slice of the kind's stack; a stack a kernel addresses in place is
        left out, as in `layer_params`)."""
        raise NotImplementedError

    def norm(self, lp, h):
        """The one norm of a block of a `pattern` model."""
        raise NotImplementedError

    def ssm_prompt(self, lp, y, length, state, i: int, row):
        """A recurrent mixer (a state-space layer whose decay its input
        sets, a scalar a head or a value a channel and state lane; linear
        attention whose decay is fixed a head: `ops/ssm.py`)
        over one whole prompt y `[1, T, hidden]` of true length `length`,
        from a ZERO state -> (out `[1, T, hidden]`, `state` with row `row`
        of layer `i` of every pool overwritten by the state after position
        `length - 1`). Positions at or past `length` (the bucket's
        padding) must leave the state as it was."""
        raise NotImplementedError

    def ssm_slice(self, lp, y, start, length, state, i: int, row):
        """`ssm_prompt` for ONE SLICE of a prompt (`prompt_slice`): y `[1,
        C, hidden]` holds positions `start .. start + C - 1`; the mixer
        starts from a zero state where `start` is 0 and from row `row` as
        the slice before left it otherwise, and writes the row back."""
        raise NotImplementedError

    def ssm_token(self, lp, y, state, i: int, rows, positions=None):
        """One token a slot of a recurrent mixer, y `[S, hidden]`: reads
        rows `rows` `[S]` of layer `i` of the pools, advances them one
        token and writes them back in place -> (out `[S, hidden]`, state).
        `positions` `[S]` are the tokens' positions, for a mixer that
        encodes them (rotary linear attention). Idle slots carry row 0;
        several may, in any order."""
        raise NotImplementedError

    # -- a model that stores entries at a rate (`rated`) -------------------

    def rated_tables(self, x, tables, rated, positions, block_size: int):
        """`tables` (`paged_attention.Tables`, or None off the kernels'
        route) as `attend_paged` gets them in a step that reads rated
        entries: a model whose kernel walks THEIR pool through the table
        adds what that walk reads beside it, once a step and not once a
        layer, and counts which way it reads them
        (`paged_attention.GATE_COUNTS`)."""
        return tables

    def store_token(self, lp, k_pool, rated, layer, block_tables, positions,
                    block_size: int):
        """A decode step's part of the rated entries: after the step's K
        and V are in the pools, write what this token completes (an entry
        every `stride` tokens) -> `rated`."""
        raise NotImplementedError

    def attend_slice(self, lp, q, k_pool, v_pool, rated, layer, block_table,
                     start, block_size: int):
        """One slice of a prompt (`prompt_slice`): the queries q `[1, C,
        ...]` of positions `start .. start + C - 1` against the CACHE SO
        FAR, layer `layer` of the pools through the sequence's table, this
        slice's K and V already in it -> (ctx `[1, C, ctx]`, `rated` with
        the entries this slice completes written)."""
        raise NotImplementedError

    def step_counters(self, positions, block_tables):
        """A small pytree of counters of ONE decode step that follow from
        the slots' positions (rows that took a sparse read, ...), or None;
        `decode_step` returns it beside the layers' counters, as `(layers'
        counters, this)`, and `step_facts` names both."""
        return None

    # -- the residual path ------------------------------------------------

    def widen(self, params: Params, x):
        """What a row CARRIES from block to block, from its embedding `x`
        `[..., hidden]` (already in the served dtype): the embedding itself
        unless the model carries more (several residual streams)."""
        return x

    def narrow(self, params: Params, x):
        """The carried state -> the `[..., hidden]` that `head` reads."""
        return x

    def res_in(self, lp, h, which: str):
        """Into a sub-layer (`which`: "attn", "mlp", or "ssm" in a
        `pattern` model): the carried state `h` -> (the sub-layer's input
        `[..., hidden]`, which its norm takes; what `res_out` is handed
        back). Per row, as every piece."""
        return h, h

    def res_out(self, lp, kept, out, which: str):
        """Out of a sub-layer: what `res_in` kept and the sub-layer's
        output -> the carried state. `out` of "attn" is the attention's
        context BEFORE its output projection: the default adds as it
        projects (`proj`)."""
        if which == "attn":
            return self.proj(lp, out, kept)
        return kept + out

    def res_counters(self, stats, *kept):
        """A block's counters: `mlp`'s `stats` with whatever the residual
        maps of the block's sub-layers count (`kept`: what each `res_in`
        of the block kept, in order), for a model whose maps count
        something; `stats` as they are otherwise."""
        return stats

    def describe(self) -> Dict:
        """What `DecodeEngine.status()["model"]` says of the model beyond
        the cache's shape (residual streams, iterations of a projection);
        {} for a model with nothing to add."""
        return {}

    def head(self, params: Params, x, prev_ids, eos_id: int):
        """Greedy next tokens [N] for the rows `x` [N, hidden]."""
        raise NotImplementedError

    def step_facts(self, stats) -> Dict:
        """Fields for a decode step's record (`decode.steps`), or a
        prompt's where `prefill_counters`, from the step's stacked
        counters, fetched to the host."""
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
             eps: float, tied: bool = False) -> jax.Array:
    """Final RMSNorm (`ln_f.scale`), an untied output head (`head.w`), or
    with `tied` the embedding `wte.w` `[vocab, hidden]` read as the head,
    and the greedy pick for the rows `x` [N, H]; `prev_ids` [N] are the
    tokens that led to them."""
    from .common import rms_norm

    x = rms_norm(x, params["ln_f.scale"], eps)
    # float32 logits: bf16 ones lie 0.03 apart near the top of a row, and
    # the greedy pick would be made among ties
    if tied:    # contracted over the embedding's lanes: no transposed copy
        logits = jax.lax.dot_general(
            x, params["wte.w"].astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return beam_top1(prev_ids.astype(jnp.int32), logits, eos_id)


def pattern_blocks(pattern: str):
    """(kind, the block's index AMONG THE BLOCKS OF ITS KIND) for every
    block of a `pattern`, in order: `MEM*` -> M 0, E 0, M 1, * 0."""
    seen: Dict[str, int] = {}
    for kind in pattern:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        yield kind, i


# a `pattern` block's kind -> what `res_in` / `res_out` call its sub-layer
_SUB_LAYER = {"M": "ssm", "E": "mlp", "*": "attn", "W": "attn"}


def mixer_layers(model: ServeModel, params: Params, x: jax.Array,
                 positions: jax.Array, k_pool: jax.Array,
                 v_pool: jax.Array, attend, state, ssm, rated=()):
    """`serve_layers` for a model of ONE mixer a block (`model.pattern`):
    the blocks one by one in the pattern's order, `h + mixer(norm(h))`
    each, every kind's parameters addressed in its own stack by the
    block's index AMONG ITS KIND, which is also its layer in the K/V pools
    (`*`), the window kind's pools (`W`) or the state pools (`M`). `attend`
    as in `serve_layers`; for a `W` block it is called with `windowed=True`
    and the program's closure takes that kind's pools and tables (they ride
    last in `rated`);
    `ssm(i, lp, y, state) -> (out, state)` is the program's recurrent
    mixer (a whole prompt, or a token a slot). Returns (x, k_pool, v_pool,
    the `E` blocks' counters stacked or None, state, rated)."""
    stats = []
    with jax.named_scope("layers"):
        for kind, i in pattern_blocks(model.pattern):
            lp = model.block_params(params, kind, i)
            if kind not in _SUB_LAYER:
                raise ValueError(f"unknown block kind {kind!r}")
            u, kept = model.res_in(lp, x, _SUB_LAYER[kind])
            y = model.norm(lp, u)
            if kind == "M":
                with jax.named_scope("ssm"):
                    out, state = ssm(i, lp, y, state)
            elif kind == "E":
                out, st = model.mlp(lp, y, params, i)
                stats.append(model.res_counters(st, kept))
            elif kind == "W":
                q, k, v = model.qkv(lp, y, positions, windowed=True)
                out, k_pool, v_pool, rated = attend(
                    jnp.int32(i), lp, q, k, v, k_pool, v_pool, rated,
                    windowed=True)
            else:
                q, k, v = model.qkv(lp, y, positions)
                out, k_pool, v_pool, rated = attend(
                    jnp.int32(i), lp, q, k, v, k_pool, v_pool, rated)
            x = model.res_out(lp, kept, out, _SUB_LAYER[kind])
    stats = None if not stats or stats[0] is None else \
        jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)
    return x, k_pool, v_pool, stats, state, rated


def serve_layers(model: ServeModel, params: Params, x: jax.Array,
                 positions: jax.Array, k_pool: jax.Array,
                 v_pool: jax.Array, attend, state=(), ssm=None, rated=()):
    """The serve programs' layer loop: `x` through every block with the
    pools in the loop's carry: the model's leading layers one by one, then
    a scan over the stacked ones (a model whose layers are all alike has
    no leading ones, and the loop is the scan). `attend(l, lp, q, k, v,
    kp, vp, rated)` is the one part the programs differ in: it gets the
    layer index, the layer's parameters and projections, the WHOLE pools
    and the pools of the entries stored at a rate (() for most models),
    writes k/v at (l, block, slot), and returns `(ctx, kp, vp, rated)`. A
    model of one mixer a block goes through `mixer_layers`, which also
    carries `state`. `x` is the CARRIED state (`ServeModel.widen`), which
    every sub-layer reads and writes through the model's `res_in` /
    `res_out`. A layer of several sub-blocks (`ServeModel.sub_blocks`) runs
    them one after another inside the one body, sub-block `j` of layer `l`
    on cache layer `l * sub_blocks + j`; what a row carries BETWEEN a
    layer's sub-blocks is whatever that model's `res_out` hands its next
    `res_in` (an expert path's result that waits for a later sub-block's
    end: models/longcat.py), and only the last `res_out` of a layer must
    give the carried state back as it came. Returns (x, k_pool, v_pool, the
    stacked layers' counters or None, state, rated); where the leading
    layers count too, `{"lead": [theirs], "stack": the stack's}`."""
    if model.pattern is not None:
        return mixer_layers(model, params, x, positions, k_pool, v_pool,
                            attend, state, ssm, rated)

    subs = model.sub_blocks

    def layer_body(carry, per_layer):
        h, kp, vp = carry
        layer, l = per_layer
        stats, kept = None, []
        for j in range(subs):
            lp, at = (layer, l) if subs == 1 else \
                (model.sub_params(layer, j), l * subs + j)
            u, kept_a = model.res_in(lp, h, "attn")
            y = model.norm_attn(lp, u)
            q, k, v = model.qkv(lp, y, positions)
            ctx, kp, vp, _ = attend(at, lp, q, k, v, kp, vp, ())
            h = model.res_out(lp, kept_a, ctx, "attn")
            u, kept_m = model.res_in(lp, h, "mlp")
            y = model.norm_mlp(lp, u)
            out, counted = model.mlp(lp, y, params, l)
            h = model.res_out(lp, kept_m, out, "mlp")
            if counted is not None:
                assert stats is None, "two sub-blocks of a layer count"
                stats = counted
            kept += [kept_a, kept_m]
        return (h, kp, vp), model.res_counters(stats, *kept)

    lead = model.lead_params(params)
    layers = jnp.arange(len(lead), k_pool.shape[0] // subs, dtype=jnp.int32)
    with jax.named_scope("layers"):
        carry = (x, k_pool, v_pool)
        lead_stats = []
        for l, lp in enumerate(lead):
            carry, st = layer_body(carry, (lp, jnp.int32(l)))
            lead_stats.append(st)
        (x, k_pool, v_pool), stats = jax.lax.scan(
            layer_body, carry, (model.layer_params(params), layers))
    if any(st is not None for st in lead_stats):
        # leading layers that count something (none did before a model's
        # residual maps counted): theirs beside the stack's
        stats = {"lead": lead_stats, "stack": stats}
    return x, k_pool, v_pool, stats, state, rated


def _split_state(model: ServeModel, state):
    """The programs' `state` as (the state row pools, the pools of the
    entries stored at a rate, the two pools of the window kind): the engine
    carries them as one tuple, in that order."""
    state = tuple(state)
    w = 2 if model.window else 0
    n = len(model.rated)
    rest = len(state) - w
    return state[:rest - n], state[rest - n:rest], state[rest:]


def _window_attend(model: ServeModel, attend_one):
    """A program's `attend` for a model with a window kind, from
    `attend_one(l, lp, q, k, v, kp, vp, rt, windowed)` over ONE kind's
    pools: a `W` block's call (`windowed=True`) is made on the window
    kind's pools, which ride last in `rated` through the layer loop."""
    def attend(l, lp, q, k, v, kp, vp, rated, windowed=False):
        *rt, wk, wv = rated
        if windowed:
            ctx, wk, wv, rt = attend_one(l, lp, q, k, v, wk, wv, tuple(rt),
                                         True)
        else:
            ctx, kp, vp, rt = attend_one(l, lp, q, k, v, kp, vp, tuple(rt),
                                         False)
        return ctx, kp, vp, (*rt, wk, wv)

    return attend


def _needs_slices(model: ServeModel):
    if model.window and not model.prompt_slice:
        raise ValueError(
            "a model with a window kind walks its prompts in slices "
            "(`prompt_slice`): a ring holds the window and ONE slice, and a "
            "whole prompt's blocks would land on each other")


def prefill(model: ServeModel, params: Params, ids: jax.Array,
            length: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
            block_table: jax.Array, state=(), row=None, window_table=None,
            *, block_size: int, eos_id: int):
    """One prompt through the stack, filling its KV blocks.

    ids [1, T] (edge-padded to the prefill bucket T), length = true
    prompt length, block_table [MB] (the sequence's row). Returns
    (first sampled token [1], k_pool, v_pool), and after the pools the
    layers' stacked counters over ALL T rows of the bucket where the model
    asks for them (`ServeModel.prefill_counters`). Padded tail positions
    write to the null block / soon-overwritten slots (see
    kv_cache.write_prefill_kv) and, being causally AFTER every real
    position, never contribute to the last real position's logits.

    A model with recurrent state also takes its `state` pools and the
    sequence's `row` in them, which the prompt overwrites from a zero
    state (`ServeModel.ssm_prompt`: the padded tail leaves the state as
    position `length - 1` left it), and returns (tok, k_pool, v_pool,
    state). A model that walks its prompts in slices (`prompt_slice`) goes
    through `prefill_sliced`; a model with a window kind always does, and
    also takes the sequence's `window_table` (`state` then ends with that
    kind's two pools).
    """
    from ..serving import kv_cache as kvc

    _needs_slices(model)
    if model.prompt_slice:
        assert not model.prefill_counters, "a sliced walk returns no counters"
        return prefill_sliced(model, params, ids, length, k_pool, v_pool,
                              block_table, state, row, window_table,
                              block_size=block_size, eos_id=eos_id)
    B, T = ids.shape
    adt = k_pool.dtype
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    with jax.named_scope("embed"):
        x = model.widen(params, model.embed(params, ids, positions)
                        .astype(adt))

    def attend(l, lp, q, k, v, kp, vp, rated):
        kp = kvc.write_prefill_kv(kp, l, k[0].reshape(T, *kp.shape[3:]),
                                  block_table, block_size)
        vp = kvc.write_prefill_kv(vp, l, v[0].reshape(T, *vp.shape[3:]),
                                  block_table, block_size)
        with jax.named_scope("attention"):
            ctx = model.attend_prompt(lp, q, k, v)
        return ctx, kp, vp, rated

    def ssm(i, lp, y, st):
        return model.ssm_prompt(lp, y, length, st, i, row)

    x, k_pool, v_pool, stats, state, _ = serve_layers(
        model, params, x, positions, k_pool, v_pool, attend, state, ssm)
    # the final norm is per row: the last real position alone goes through
    last = jnp.maximum(length, 1) - 1
    tok = model.head(params, model.narrow(params, x[0, last][None]),
                     ids[0, last][None], eos_id)
    counters = (stats,) if model.prefill_counters else ()
    if state:
        return (tok, k_pool, v_pool) + counters + (state,)
    return (tok, k_pool, v_pool) + counters


def prefill_sliced(model: ServeModel, params: Params, ids: jax.Array,
                   length: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   block_table: jax.Array, state=(), row=None,
                   window_table=None, *, block_size: int, eos_id: int):
    """`prefill` for a model that walks a prompt in SLICES
    (`ServeModel.prompt_slice`): ids [1, T] go through the whole stack C =
    min(T, prompt_slice) tokens at a time, in ONE program. A slice's K and
    V are written (whole blocks: C is whole blocks) before its queries
    attend to the cache so far through the sequence's table
    (`attend_slice`, which also writes the rated entries the slice
    completes), and a recurrent mixer starts each slice from the row the
    slice before left (`ssm_slice`), so no temporary grows with T: a
    32k-token prompt costs the memory of a 2k one. Only the slices that
    hold real tokens run; the slice of position `length - 1` gives the
    first token. A `W` block's K/V go into the window kind's pools (the last
    two of `state`) through the sequence's `window_table`, a ring repeated:
    a slice's blocks land on what fell out of every later query's reach.
    Returns what `prefill` returns."""
    from ..serving import kv_cache as kvc

    _, T = ids.shape
    C = min(T, int(model.prompt_slice))
    if T % C or C % block_size:
        raise ValueError(
            f"a prompt walked in slices needs a bucket of whole slices of "
            f"whole blocks: bucket {T}, slice {C}, block {block_size}")
    adt = k_pool.dtype
    rows_state, rated, windowed = _split_state(model, state)
    per_slice = C // block_size
    # the blocks a prompt of this bucket can own: what a slice's queries
    # gather and score is bounded by the bucket, not by the table's width
    block_table = block_table[:T // block_size]
    if windowed:
        window_table = window_table[:T // block_size]

    def one(s, carry):
        kp, vp, st, rt, last_x = carry
        start = s * C
        slice_ids = jax.lax.dynamic_slice_in_dim(ids, start, C, axis=1)
        positions = (start + jnp.arange(C, dtype=jnp.int32))[None]
        with jax.named_scope("embed"):
            x = model.widen(params, model.embed(params, slice_ids, positions)
                            .astype(adt))

        def attend(l, lp, q, k, v, kp, vp, rt, of_window=False):
            table = window_table if of_window else block_table
            blocks = jax.lax.dynamic_slice_in_dim(
                table, s * per_slice, per_slice)
            kp = kvc.write_prefill_kv(kp, l, k[0].reshape(C, *kp.shape[3:]),
                                      blocks, block_size)
            vp = kvc.write_prefill_kv(vp, l, v[0].reshape(C, *vp.shape[3:]),
                                      blocks, block_size)
            if of_window:
                with jax.named_scope("window_attention"):
                    ctx, rt = model.attend_slice(
                        lp, q, kp, vp, rt, l, table, start, block_size,
                        window=model.window)
                return ctx, kp, vp, rt
            with jax.named_scope("attention"):
                ctx, rt = model.attend_slice(lp, q, kp, vp, rt, l,
                                             block_table, start, block_size)
            return ctx, kp, vp, rt

        if windowed:
            attend = _window_attend(model, attend)

        def ssm(i, lp, y, st):
            return model.ssm_slice(lp, y, start, length, st, i, row)

        x, kp, vp, _, st, rt = serve_layers(
            model, params, x, positions, kp, vp, attend, st, ssm, rt)
        # the last real position lies in the last slice that runs
        at = jnp.clip(jnp.maximum(length, 1) - 1 - start, 0, C - 1)
        return kp, vp, st, rt, x[0, at]

    # the window kind's pools ride last in what the layer loop threads
    rated = rated + windowed

    # what ONE row carries, by the model (`widen`): `[hidden]` by default
    carried = jax.eval_shape(
        lambda p: model.widen(p, model.embed(
            p, ids[:, :1], jnp.zeros((1, 1), jnp.int32)).astype(adt)),
        params).shape[2:]
    live = jnp.clip(-(-length // C), 1, T // C)
    k_pool, v_pool, rows_state, rated, last_x = jax.lax.fori_loop(
        0, live, one,
        (k_pool, v_pool, rows_state, rated, jnp.zeros(carried, adt)))
    last = jnp.maximum(length, 1) - 1
    tok = model.head(params, model.narrow(params, last_x[None]),
                     ids[0, last][None], eos_id)
    state = rows_state + rated
    if state:
        return tok, k_pool, v_pool, state
    return tok, k_pool, v_pool


def _window_mask(window: Optional[int], t, tok):
    """Whether key `tok` is one of the newest `window` that query `t` sees
    (`t - tok < window`: `window` keys, the query's own among them); True
    everywhere without a window. The causal `tok <= t` is the caller's."""
    return True if window is None else t - tok < window


def gqa_prompt(q: jax.Array, k: jax.Array, v: jax.Array, heads: int,
               kv_heads: int, scale: Optional[float] = None,
               window: Optional[int] = None) -> jax.Array:
    """Causal grouped-query attention of whole sequences from their own
    projections: q `[B, T, heads*D]`, k and v `[B, T, kv_heads*D]`; the
    `heads / kv_heads` query heads of a K/V head read it together, scores
    and softmax in float32 at `scale` (None: `1/sqrt(D)`), over the newest
    `window` keys alone where one is given -> `[B, T, heads*D]`."""
    B, T = q.shape[:2]
    D = q.shape[-1] // heads
    q = q.reshape(B, T, kv_heads, heads // kv_heads, D)
    k = k.reshape(B, T, kv_heads, D)
    v = v.reshape(B, T, kv_heads, D)
    scores = jnp.einsum("btgrd,bsgd->bgrts", q, k,
                        preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(D) if scale is None else scale)
    seen = jnp.tril(jnp.ones((T, T), bool))
    if window is not None:
        at = jnp.arange(T, dtype=jnp.int32)
        seen = seen & _window_mask(window, at[:, None], at[None, :])
    att = jax.nn.softmax(jnp.where(seen, scores, -1e9), axis=-1)
    ctx = jnp.einsum("bgrts,bsgd->btgrd", att.astype(v.dtype), v)
    return ctx.reshape(B, T, heads * D)


SLICE_KEYS = 1024   # keys a chunk of a prompt slice's walk (`gqa_slice`)


def gqa_slice(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, layer,
              block_table: jax.Array, start, block_size: int, heads: int,
              kv_heads: int, scale: Optional[float] = None,
              window: Optional[int] = None) -> jax.Array:
    """Grouped-query attention of ONE SLICE of a prompt against the cache so
    far (an `attend_slice` for plain K and V): the queries q `[1, C,
    heads*D]` of positions `start .. start + C - 1` against layer `layer` of
    the pools through the sequence's table, this slice's K and V already in
    it. The keys are walked in chunks of `SLICE_KEYS` tokens up to the
    slice's own, each gathered through the table and met by all the
    slice's queries under an online softmax: float32 scores of `heads x C x
    SLICE_KEYS` whatever the prompt's length -> `[1, C, heads*D]`. Under a
    `window` the walk starts at the chunk of the first key the slice's FIRST
    query sees (`start - window + 1`) and a query sees its newest `window`
    keys alone: a 32k prompt's slice computes `window + C` keys, not 32k."""
    from ..serving import kv_cache as kvc

    f32 = jnp.float32
    C = q.shape[1]
    D = q.shape[-1] // heads
    G, R = kv_heads, heads // kv_heads
    chunk = min(SLICE_KEYS, C)
    if C % chunk or chunk % block_size:
        raise ValueError(
            f"a slice of {C} queries walks its keys in whole chunks of "
            f"{chunk} tokens of whole blocks of {block_size}")
    per_chunk = chunk // block_size
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qh = q[0].reshape(C, G, R, D)
    t = start + jnp.arange(C, dtype=jnp.int32)

    def one(i, carry):
        m, l, acc = carry
        blocks = jax.lax.dynamic_slice_in_dim(
            block_table, i * per_chunk, per_chunk)[None]
        kch = kvc.gather_kv(k_pool, layer, blocks)[0].reshape(chunk, G, D)
        vch = kvc.gather_kv(v_pool, layer, blocks)[0].reshape(chunk, G, D)
        sc = jnp.einsum("qgrd,kgd->grqk", qh, kch,
                        preferred_element_type=f32) * scale
        tok = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ok = (tok[None, :] <= t[:, None]) \
            & _window_mask(window, t[:, None], tok[None, :])
        ok = ok[None, None]                               # [1, 1, C, chunk]
        sc = jnp.where(ok, sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "grqk,kgd->grqd", p.astype(vch.dtype), vch,
            preferred_element_type=f32)
        return m_new, l, acc

    first = 0 if window is None \
        else jnp.maximum(start - (int(window) - 1), 0) // chunk
    _, l, acc = jax.lax.fori_loop(
        first, (start + C) // chunk, one,
        (jnp.full((G, R, C, 1), -1e30, f32), jnp.zeros((G, R, C, 1), f32),
         jnp.zeros((G, R, C, D), f32)))
    ctx = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)      # [G, R, C, D]
    return ctx.transpose(2, 0, 1, 3).reshape(1, C, heads * D)


def mha_cached(q: jax.Array, keys: jax.Array, vals: jax.Array,
               pos: jax.Array, heads: int, kv_heads: Optional[int] = None,
               scale: Optional[float] = None,
               window: Optional[int] = None) -> jax.Array:
    """Multi-head attention of query rows over a gathered context (the
    default `ServeModel.attend_cached`): q `[S, W, heads*head_dim]`, keys
    and vals `[S, M, kv_heads*head_dim]`, row (s, w) sees key positions
    `<= pos[s, w]` -> `[S, W, heads*head_dim]`. With fewer K/V heads than
    query heads, a K/V head's query heads read it together, at `scale`
    where a model's softmax is not at `1/sqrt(head_dim)`; under `window`
    the newest `window` of those positions alone (the context is then a
    ring read through its repeated table: what lies at an older position
    is a newer key's, and masked)."""
    S, W, width = q.shape
    m = keys.shape[1]
    hd = width // heads
    if window is not None and (kv_heads is None or kv_heads == heads):
        raise NotImplementedError("a window over multi-head K/V")
    if kv_heads is not None and kv_heads != heads:
        q = q.reshape(S, W, kv_heads, heads // kv_heads, hd)
        keys = keys.reshape(S, m, kv_heads, hd)
        vals = vals.reshape(S, m, kv_heads, hd)
        scores = jnp.einsum("swgrd,smgd->swgrm", q, keys) \
            * (1.0 / math.sqrt(hd) if scale is None else scale)
        at = jnp.arange(m, dtype=jnp.int32)[None, None, :]
        mask = (at <= pos[:, :, None]) \
            & _window_mask(window, pos[:, :, None], at)
        scores = jnp.where(mask[:, :, None, None, :], scores, -1e9)
        att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        ctx = jnp.einsum("swgrm,smgd->swgrd", att.astype(keys.dtype), vals)
        return ctx.reshape(S, W, width)
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
                     block_tables: jax.Array, pos: jax.Array,
                     rated=(), scope: str = "attention") -> jax.Array:
    """Attention of the query rows q `[S, W, ...]` over a gathered copy of
    every slot's whole table: layer `layer` of the pools through
    block_tables `[S, MB]`, key positions `<= pos[s, w]`, by `attend(q,
    keys, vals, pos)`, a model's `attend_cached` with its layer's
    parameters bound -> `[S, W, ctx]`. What `prefill_chunk` and
    `verify_step` run everywhere and `decode_step` off the TPU, and what a
    paged kernel is compared with on it. The pools of entries stored at a
    rate (`rated`) are gathered through the same tables and handed over as
    `extra`."""
    from ..serving import kv_cache as kvc

    keys = kvc.gather_kv(k_pool, layer, block_tables)   # [S, M, width]
    vals = kvc.gather_kv(v_pool, layer, block_tables)
    with jax.named_scope("kv_gather"):
        extra = tuple(kvc.gather_rated(p, layer, block_tables)
                      for p in rated)
    with jax.named_scope(scope):
        if extra:
            return attend(q, keys, vals, pos, extra)
        return attend(q, keys, vals, pos)


def decode_step(model: ServeModel, params: Params, ids: jax.Array,
                positions: jax.Array, k_pool: jax.Array,
                v_pool: jax.Array, block_tables: jax.Array, state=(),
                rows=None, window_tables=None, *, block_size: int,
                eos_id: int):
    """One decode step for S resident slots.

    ids [S] (each slot's previous token), positions [S] (where this
    token's K/V lands = current sequence length), block_tables [S, MB].
    Every row's math touches only that row's activations and its own
    blocks, so a slot's tokens are bit-identical whatever else shares
    the batch — the property test_decode's admit-mid-decode test pins.
    Returns (next tokens [S], k_pool, v_pool, the layers' stacked
    counters or None: `ServeModel.mlp`). A model with recurrent state also
    takes its `state` pools and each slot's row `rows` [S] (0, the null
    row, for an idle slot), advances the rows in place
    (`ServeModel.ssm_token`) and returns the pools as a fifth result; the
    pools of entries stored at a rate (`ServeModel.rated`) follow in
    `state`, written by `store_token` and read by the attention pieces, and
    the window kind's two pools (`ServeModel.window`) are its last, written
    and read through `window_tables` `[S, MB]`: a `W` block's walk starts at
    its window's first block."""
    from ..ops.pallas import paged_attention as pa
    from ..serving import kv_cache as kvc

    S = ids.shape[0]
    adt = k_pool.dtype
    with jax.named_scope("embed"):
        x = model.widen(params, model.embed(params, ids, positions)
                        .astype(adt))

    # the one gate (ops/pallas/paged_attention.py, asked through the model,
    # whose cache it is): on a TPU a kernel reads the live blocks through
    # the table; elsewhere the gathered form below
    route = model.paged_route(x, k_pool, v_pool)
    pa.GATE_COUNTS[route or "gather"] += 1
    # what the kernel's walk may fetch in one copy, counted once a step
    # and not once a layer
    tables = pa.with_runs(block_tables, k_pool, v_pool) if route else None
    rows_state, rated, windowed = _split_state(model, state)
    if rated:
        tables = model.rated_tables(x, tables, rated, positions,
                                    block_size)
    wroute = wtables = None
    if windowed:
        # the window kind asks the same gate over its own pools, and its
        # walk's tables (from each slot's window's first block on) are
        # counted once a step too
        wroute = model.paged_route(x, *windowed)
        pa.GATE_COUNTS[wroute + "_window" if wroute
                       else "gather_window"] += 1
        if wroute:
            wtables = pa.window_tables(window_tables, positions,
                                       model.window, *windowed)

    def attend(l, lp, q, k, v, kp, vp, rt, of_window=False):
        if of_window:
            kp = kvc.write_token_kv(kp, l, k.reshape(S, *kp.shape[3:]),
                                    window_tables, positions, block_size)
            vp = kvc.write_token_kv(vp, l, v.reshape(S, *vp.shape[3:]),
                                    window_tables, positions, block_size)
            if wroute:
                with jax.named_scope("window_attention"):
                    ctx = model.attend_paged(lp, q, kp, vp, l, wtables,
                                             positions, window=model.window)
            else:
                ctx = cached_attention(
                    functools.partial(model.attend_cached, lp,
                                      window=model.window), q[:, None], kp,
                    vp, l, window_tables, positions[:, None],
                    scope="window_attention")[:, 0]
            return ctx, kp, vp, rt
        kp = kvc.write_token_kv(kp, l, k.reshape(S, *kp.shape[3:]),
                                block_tables, positions, block_size)
        vp = kvc.write_token_kv(vp, l, v.reshape(S, *vp.shape[3:]),
                                block_tables, positions, block_size)
        if rt:
            with jax.named_scope("attention"):
                rt = model.store_token(lp, kp, rt, l, block_tables,
                                       positions, block_size)
        if route:
            with jax.named_scope("attention"):
                ctx = model.attend_paged(lp, q, kp, vp, l, tables,
                                         positions, *((rt,) if rt else ()))
        else:
            ctx = cached_attention(
                functools.partial(model.attend_cached, lp), q[:, None], kp,
                vp, l, block_tables, positions[:, None], rt)[:, 0]
        return ctx, kp, vp, rt

    def ssm(i, lp, y, st):
        return model.ssm_token(lp, y, st, i, rows, positions)

    if windowed:
        attend = _window_attend(model, attend)
    x, k_pool, v_pool, stats, rows_state, rated = serve_layers(
        model, params, x, positions, k_pool, v_pool, attend, rows_state,
        ssm, rated + windowed)
    counters = model.step_counters(positions, block_tables)
    if counters is not None:
        stats = (stats, counters)
    state = rows_state + rated
    tok = model.head(params, model.narrow(params, x), ids, eos_id)
    if state:
        return tok, k_pool, v_pool, stats, state
    return tok, k_pool, v_pool, stats


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

    if model.window:
        raise ValueError("a model with a window kind has no chunked prefill")
    _, C = ids.shape
    adt = k_pool.dtype
    pos = start + jnp.arange(C, dtype=jnp.int32)
    # the final slice's padded tail can run past the positions the model
    # addresses; clamp (those rows' outputs are never consumed, their KV
    # lands in the null block / overwritten slots)
    with jax.named_scope("embed"):
        x = model.widen(params, model.embed(
            params, ids[0], jnp.minimum(pos, model.max_len - 1)).astype(adt))

    def attend(l, lp, q, k, v, kp, vp, rated):
        kp = kvc.write_chunk_kv(kp, l, k.reshape(C, *kp.shape[3:]),
                                block_table, start, block_size)
        vp = kvc.write_chunk_kv(vp, l, v.reshape(C, *vp.shape[3:]),
                                block_table, start, block_size)
        # one "slot" of C query rows over the sequence's own table
        ctx = cached_attention(
            functools.partial(model.attend_cached, lp), q[None], kp, vp, l,
            block_table[None], pos[None])[0]
        return ctx, kp, vp, rated

    x, k_pool, v_pool, *_ = serve_layers(model, params, x, pos, k_pool,
                                         v_pool, attend)
    last = jnp.clip(length - 1 - start, 0, C - 1)
    tok = model.head(params, model.narrow(params, x[last][None]),
                     ids[0, last][None], eos_id)
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

    if model.window:
        raise ValueError("a model with a window kind has no verify step")
    S, W = ids.shape
    adt = k_pool.dtype
    pos = positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    with jax.named_scope("embed"):
        x = model.widen(params, model.embed(
            params, ids, jnp.minimum(pos, model.max_len - 1)).astype(adt))

    def attend(l, lp, q, k, v, kp, vp, rated):
        kp = kvc.write_span_kv(kp, l, k.reshape(S, W, *kp.shape[3:]),
                               block_tables, positions, block_size)
        vp = kvc.write_span_kv(vp, l, v.reshape(S, W, *vp.shape[3:]),
                               block_tables, positions, block_size)
        ctx = cached_attention(functools.partial(model.attend_cached, lp),
                               q, kp, vp, l, block_tables, pos)
        return ctx, kp, vp, rated

    x, k_pool, v_pool, *_ = serve_layers(model, params, x, pos, k_pool,
                                         v_pool, attend)
    tokens = model.head(params, model.narrow(params, x).reshape(S * W, -1),
                        ids.reshape(S * W), eos_id).reshape(S, W)
    return tokens, k_pool, v_pool
