"""Plain float32 Trinity (`model_type` `afmoe`) forward pass, written from the
model's `config.json` (arcee-ai/Trinity-Mini) and the published modelling
code's order of operations (`modeling_afmoe.py`):

    h0      = sqrt(hidden) * E[ids]                          mup_enabled
    layer l (pattern[l] = "W" sliding | "*" full; a dense MLP where
             l < dense_layers, experts after):
      a     = RMSNorm_in(h)                                  eps 1e-5
      q     = RMSNorm_q(a Wq as heads x D);  k = RMSNorm_k(a Wk as kv x D)
      v     = a Wv as kv x D;  g = a Wg
      W: q, k = rotary(q, k; theta, rotate-half, all D lanes);  *: none
      s_ij  = q_i . k_j / sqrt(D), j <= i, and in a W layer i - j < window
              (`window` keys, the query's own among them); query head h
              reads K/V head h // (heads / kv_heads)
      o     = ((softmax_j s) v * sigmoid(g)) Wo
      h     = h + RMSNorm_post_attn(o)
      m     = RMSNorm_pre_mlp(h)
      f     = dense:   Wdown(silu(Wgate m) * Wup m)
              experts: shared(m) + sum over the top_k picked w_e expert_e(m)
      h     = h + RMSNorm_post_mlp(f)
    logits  = RMSNorm(h) W_head                              untied
    router: s = sigmoid(m Wr), float32; pick the top_k of s + expert_bias;
            w = s[picked] / (sum s[picked] + 1e-20) * route_scale

`held` = (first, past the last) of the routed experts whose term is
computed (a chip's share of a layer: a pick of an expert outside it
contributes nothing here, as in the program; None: all `n_experts`). The
stacks `blk.w_*` hold either exactly the held experts or all of them.

Straightforward jax.numpy, one unbatched row of tokens at a time: no cache,
no ring, no slices, no engine, no kernel, no sorting of tokens, no code of
the program. Every held expert is computed for every token and masked by
the router's choice (a plain loop); attention goes a block of `Q_BLOCK`
query rows at a time (a W layer's against the `window + Q_BLOCK` keys it can
see, which is the same sum). The model's layers are passed a BLOCK at a time
under the prefix `blk.`: block 2l is layer l's attention, block 2l + 1 its
MLP (`blocks(model["pattern"])`), each with the norm before it
(`norm.scale`) and after it (`post.scale`).

Departures from the published code: the fused gate/up of an MLP are two
matrices; `layer_types` is the string `pattern`.

The switches of `model` exist for the tests and the controls that show what
the comparison tells apart; their defaults are the published model. `window`,
`route_scale` and `mup_enabled` are the model's own keys (a control changes
them in the reference's copy). `window_all` (True: every layer full),
`rope_full` (True: rotary on the full layers too), `rope_sliding` (False:
none on the sliding ones), `output_gate` (False), `qk_norm` (False),
`post_norms` (False), `norm_topk` (False: the kept scores as they are),
`bias_selects` (False: the top_k of the scores alone), `shared_expert`
(False), `held_term` (False: the routed experts' term dropped; "all": the
ABSENT experts' term added, the uncut layer: the stacks then hold all
`n_experts`). Three faults of a RING a real cache could have, as the
positions of the keys a query would then read (`prompt_len` tells the
prompt from what was generated; `prompt_slice` and `block`, 16, are the
program's walk; `q_block`, the query rows a block of attention, is at most
a slice):
`ring_short` (n: the ring n blocks shorter than window + slice + a block: a
slice's blocks land on keys its own first queries still read, which then
read the K/V of the position a ring later), `pad_tail` (n: the n oldest
keys that the query block of the first generated token sees hold the
prompt's last token, as if a bucket's padded tail had landed on them), `stale_ring` (n: the n keys
BEFORE a query's window count too, and hold another position's K/V: a
ring's last holder read through a mask that is a ring too long)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # query rows a block of attention (`model["q_block"]`)


def q_block(model) -> int:
    return int(model.get("q_block", Q_BLOCK))


def blocks(pattern: str) -> str:
    return "".join(kind + "E" for kind in pattern)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope_half(x, theta):
    """x [T, heads, d] at positions 0..T-1, pairs (i, i + d/2)."""
    T, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _key_source(model, first, T, prompt_len):
    """For the query block that starts at `first`: of every key slot j the
    position whose K/V it holds (j itself in a sound cache)."""
    j = jnp.arange(T)
    src = j
    short, pad = int(model.get("ring_short", 0)), int(model.get("pad_tail", 0))
    if short and prompt_len is not None:
        C, bs = int(model["prompt_slice"]), int(model.get("block", 16))
        ring = ((model["window"] + C) // bs + 1 - short) * bs
        end = (first // C + 1) * C          # the slice's end: all written
        lost = (j < end - ring) & (first < prompt_len)
        src = jnp.where(lost, jnp.minimum(j + ring, T - 1), src)
    if pad and prompt_len is not None:
        # the query block of the first generated token, and the oldest key
        # its first row sees
        after = prompt_len // q_block(model) * q_block(model)
        oldest = after - model["window"] + 1
        lost = (j >= oldest) & (j < oldest + pad) & (first >= after)
        src = jnp.where(lost, prompt_len - 1, src)
    return src


def _attention(lp, y, model, kind, prompt_len=None):
    """Causal grouped-query attention with the output gate, a block of
    query rows at a time; `kind` "W": the newest `window` keys alone."""
    T = y.shape[0]
    nh, kvh, d = model["heads"], model["kv_heads"], model["head_dim"]
    eps = model.get("rms_eps", 1e-5)
    sliding = kind == "W" and not model.get("window_all", False)
    q = (y @ lp["blk.wq"]).reshape(T, nh, d)
    k = (y @ lp["blk.wk"]).reshape(T, kvh, d)
    v = (y @ lp["blk.wv"]).reshape(T, kvh, d)
    if model.get("qk_norm", True):
        q = _rms(q, lp["blk.q_norm.scale"], eps)
        k = _rms(k, lp["blk.k_norm.scale"], eps)
    if model.get("rope_sliding", True) if kind == "W" \
            else model.get("rope_full", False):
        theta = model.get("rope_theta", 10000.0)
        q, k = _rope_half(q, theta), _rope_half(k, theta)
    q = q.reshape(T, kvh, nh // kvh, d)
    Q_BLOCK = q_block(model)
    window = int(model["window"]) if sliding else T
    stale = int(model.get("stale_ring", 0)) if sliding else 0
    span = min(T, -(-(window + stale + Q_BLOCK) // Q_BLOCK) * Q_BLOCK)
    faulty = any(model.get(f) for f in ("ring_short", "pad_tail")) and sliding

    def rows(first):
        # the keys this block can see lie in [lo, lo + span)
        lo = jnp.clip(first + Q_BLOCK - span, 0, T - span)
        qb = jax.lax.dynamic_slice_in_dim(q, first, Q_BLOCK)
        if faulty:
            src = jax.lax.dynamic_slice_in_dim(
                _key_source(model, first, T, prompt_len), lo, span)
            kb, vb = k[src], v[src]
        else:
            kb = jax.lax.dynamic_slice_in_dim(k, lo, span)
            vb = jax.lax.dynamic_slice_in_dim(v, lo, span)
        t = (first + jnp.arange(Q_BLOCK))[:, None]
        j = (lo + jnp.arange(span))[None, :]
        seen = (j <= t) & (t - j < window)
        s = jnp.einsum("qgrd,kgd->grqk", qb, kb) / math.sqrt(d)
        if stale:       # n keys before the window, another position's K/V
            other = (lo + jnp.arange(span) * 7 + 3) % T
            ghost = (t - j >= window) & (t - j < window + stale)
            s = jnp.where(ghost[None, None], jnp.einsum(
                "qgrd,kgd->grqk", qb, k[other]) / math.sqrt(d), s)
            seen = seen | ghost
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("grqk,kgd->qgrd",
                         jnp.where(ghost[None, None], 0.0, p) if stale else p,
                         vb)
        if stale:
            out = out + jnp.einsum(
                "grqk,kgd->qgrd", jnp.where(ghost[None, None], p, 0.0),
                v[other])
        return out.reshape(Q_BLOCK, nh * d)

    ctx = jax.lax.map(rows, jnp.arange(0, T, Q_BLOCK)).reshape(T, nh * d)
    if model.get("output_gate", True):
        ctx = ctx * jax.nn.sigmoid(y @ lp["blk.wg"])
    return ctx @ lp["blk.wo"]


def route(scores, bias, model):
    """[T, E] weights, zero off a token's top_k: the published rule."""
    k = model["top_k"]
    pick = scores + bias if model.get("bias_selects", True) else scores
    kth = jnp.sort(pick, axis=-1)[:, -k][:, None]
    w = jnp.where(pick >= kth, scores, 0.0)
    if model.get("norm_topk", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * model.get("route_scale", 1.0)


def _swiglu(y, gate, up, down):
    return (_silu(y @ gate) * (y @ up)) @ down


def experts(lp, y, model):
    """sum_e w_e expert_e(y) over each token's chosen experts that are
    HELD, plus the shared expert; y [T, H]."""
    E = model["n_experts"]
    first, past = model.get("held") or (0, E)
    w = route(jax.nn.sigmoid(y @ lp["blk.router"]), lp["blk.router_bias"],
              model)
    # the stacks hold the held experts alone, or all of them
    offset = first if lp["blk.w_gate"].shape[0] == past - first else 0
    if model.get("held_term", True) == "all":
        first, past = 0, E

    def one(e, acc):
        out = _swiglu(y, lp["blk.w_gate"][e - offset],
                      lp["blk.w_up"][e - offset], lp["blk.w_down"][e - offset])
        return acc + w[:, e][:, None] * out

    out = jnp.zeros_like(y)
    if model.get("held_term", True):
        out = jax.lax.fori_loop(first, past, one, out)
    if model.get("shared_expert", True):
        out = out + _swiglu(y, lp["blk.shared_gate"], lp["blk.shared_up"],
                            lp["blk.shared_down"])
    return out


def block(lp, x, model, kind, prompt_len=None):
    """One block of `kind` ("W" | "*" | "E") for the tokens x [T, H] at
    positions 0..T-1."""
    eps = model.get("rms_eps", 1e-5)
    y = _rms(x, lp["blk.norm.scale"], eps)
    if kind == "E":
        out = _swiglu(y, lp["blk.mlp_gate"], lp["blk.mlp_up"],
                      lp["blk.mlp_down"]) if "blk.mlp_gate" in lp \
            else experts(lp, y, model)
    elif kind in ("W", "*"):
        out = _attention(lp, y, model, kind, prompt_len)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if model.get("post_norms", True):
        out = _rms(out, lp["blk.post.scale"], eps)
    return x + out


def stack_of(model, b):
    kind = blocks(model["pattern"])[b]
    if kind != "E":
        return {"W": "wattn", "*": "attn"}[kind]
    return "dense" if b // 2 < model["dense_layers"] else "moe"


def layer_of(params, model, b):
    """Block b's parameters out of the program's flat set (the blocks of a
    stack under the stack's prefix, in the pattern's order)."""
    stack = stack_of(model, b)
    nth = sum(1 for c in range(b) if stack_of(model, c) == stack)
    prefix = stack + "."
    return {"blk." + k[len(prefix):]: v[nth] for k, v in params.items()
            if k.startswith(prefix)}


def embed(params, model, ids):
    scale = math.sqrt(model["hidden"]) if model.get("mup_enabled", True) \
        else 1.0
    return scale * params["wte.w"][ids]


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-5))
    return rows @ params["head.w"]


def logits_rows(params, model, ids, first, n_rows, prompt_len=None):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T] (T whole `q_block`s), `params` holding every block
    (stacked); row t predicts token t + 1."""
    x = embed(params, model, ids)
    for b, kind in enumerate(blocks(model["pattern"])):
        x = block(layer_of(params, model, b), x, model, kind, prompt_len)
    return head_rows(params, model, x, first, n_rows)


MEAN_TIMES = 16     # the mean's weight beside the worst token (`verdict`)
SPARED = 8          # of every 64 sampled tokens, the worst are not judged


def spared(n_tokens: int) -> int:
    return n_tokens * SPARED // 64


def verdict(gaps) -> float:
    """One number of the sampled tokens' gaps for the tolerance, as
    `longcat_ref.verdict` takes it: the WORST token's, or `MEAN_TIMES` the
    MEAN where that is larger, over the tokens WITHOUT the `spared` largest
    gaps, an eighth of the sample.

    Why an eighth is set aside here too. The layer is a SHARE: half the
    routed experts are held, and the router's 8th and 9th scores lie close,
    so rounding to bf16 flips a row's last pick now and then; where the flip
    crosses the edge of the held range that token gains or loses a whole
    expert's term, one token of the fault "held term dropped" itself. What
    tells rounding from a fault is HOW MANY tokens move."""
    gaps = np.sort(np.asarray(gaps, np.float64))
    rest = gaps[:len(gaps) - spared(len(gaps))]
    return float(max(rest.max(), MEAN_TIMES * rest.mean()))


def stream_rows(top, layer, model, prompts, streams, width, weights=None):
    """The float32 logits `[len(stream), vocab]` that predict each stream's
    tokens after its prompt, teacher-forced. `top` holds the parameters
    outside the layers, `layer(b)` gives block b's in float32: the
    sequences go through one block at a time, and only that block's weights
    need to exist. Rows are padded to the longest stream's length (at most
    `width`), rounded up to whole `Q_BLOCK`s, so that one program a block
    kind serves every stream; a causal model keeps the padding out of every
    row that is read. `weights(name, value)` is a control on the parameters
    (rounding them to a lower precision), applied a tensor at a time."""
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, jnp.asarray(v, jnp.float32))
           for k, v in top.items()}
    n_new = len(streams[0])
    longest = max(len(p) for p in prompts) + n_new
    width = -(-min(int(width), longest) // q_block(model)) * q_block(model)
    order = blocks(model["pattern"])
    step = jax.jit(lambda lp, x, n, kind: block(lp, x, model, kind, n),
                   static_argnums=3)
    head = jax.jit(lambda p, x, first: head_rows(p, model, x, first, n_new))
    with jax.default_matmul_precision("highest"):
        xs = []
        for prompt, generated in zip(prompts, streams):
            ids = np.zeros((width,), np.int32)
            ids[:len(prompt) + n_new] = list(prompt) + list(generated)
            xs.append(embed(top, model, jnp.asarray(ids)))
        for b, kind in enumerate(order):
            lp = {k: weights(k, jnp.asarray(v, jnp.float32))
                  for k, v in layer(b).items()}
            xs = [step(lp, x, np.int32(len(p)), kind)
                  for x, p in zip(xs, prompts)]
            del lp
        return [np.asarray(head(top, x, np.int32(len(prompt) - 1)),
                           np.float32) for x, prompt in zip(xs, prompts)]


def gaps_of(rows, picks):
    """How far each pick lies below its row's best, all streams'."""
    gaps = []
    for r, p in zip(rows, picks):
        gaps.extend(r.max(axis=-1) - r[np.arange(len(p)), np.asarray(p)])
    return gaps


def stream_gaps(top, layer, model, prompts, streams, width):
    """For each (prompt, generated tokens): how far, in float32 logits, each
    generated token lies below the reference's own argmax at its position,
    teacher-forced (`stream_rows`). Returns (`verdict` of all the gaps,
    tokens equal to the argmax)."""
    rows = stream_rows(top, layer, model, prompts, streams, width)
    exact = sum(int((r.argmax(axis=-1) == np.asarray(g)).sum())
                for r, g in zip(rows, streams))
    return verdict(gaps_of(rows, streams)), exact
