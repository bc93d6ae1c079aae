"""Plain float32 Granite 4.0-H forward pass, written from the model's
`config.json` (ibm-granite/granite-4.0-h-small, `model_type`
`granitemoehybrid`) and the published modelling code's order of operations:

    h0      = embedding_multiplier * E[ids]
    layer l (pattern[l] = "M" | "*"):
      h     = h + residual_multiplier * mixer_l(RMSNorm(h))       eps 1e-5
      y     = RMSNorm(h)
      h     = h + residual_multiplier * (experts(y) + shared(y))
    logits  = (RMSNorm(h) E^T) / logits_scaling                   tied head

    M   [z | xBC | dt] = y W_in              inner | inner + 2 G N | heads
        xBC = silu(conv(xBC) + b)            depthwise, causal, 4 taps
        [x | B | C] = xBC                    heads x P | G x N | G x N
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
            a head at a time, B and C of the head's group (ONE group
            published), S_0 = 0
        out = (RMSNorm over groups of inner / G of (y * silu(z)) * w) W_out
    *   q = y W_q -> heads x D; k, v -> kv_heads x D; query head h reads
        K/V head h // (heads / kv_heads); causal softmax at
        attention_multiplier; NO position enters; out = ctx W_o
    experts: logits = y W_r; the top_k largest LOGITS; weights = softmax
        over those top_k (the published order: top-k first, softmax after);
        expert e: down_e(silu(gate_e y) * up_e y); shared the same, added
        unweighted

`held` = (first, past the last) of the routed experts whose term is
computed (a chip's share of a layer: a pick of an expert outside it
contributes nothing here, as in the program; None: all `n_experts`). The
stacks `blk.w_*` hold either exactly the held experts or all of them.

The recurrence runs TOKEN BY TOKEN (a `lax.scan`), the experts as a plain
loop with the router's choice as a mask: no chunks, no slices, no cache, no
state pool, no sorting of tokens, no code of the program. One unbatched row
of tokens at a time. The model's layers are passed a BLOCK at a time under
the prefix `blk.`: block 2l is layer l's mixer, block 2l + 1 its experts
(`blocks(model["pattern"])`), each with the norm that precedes it.

Departures from the published code: the fused `input_linear` (gate's
columns then up's) is two matrices `w_gate`, `w_up`; the Mamba mixer's
`in_proj` order is z | xBC | dt as published.

The switches of `model` exist for the tests and the controls that show what
the comparison tells apart; their defaults are the published model. The
four multipliers are keys of `model` themselves (`residual_multiplier` 1.0,
`embedding_multiplier` 1.0, `attention_multiplier` 1/sqrt(head_dim),
`logits_scaling` 1.0 are the faults). `rope` (True: rotary positions on q
and k), `norm_groups` (n: the gated norm in n groups), `bc_per_head` (True:
head h reads B rolled by h lanes and C by 2h, as if each head had its own),
`skip_D`, `dt_bias` (False), `conv_bias` (False), `shared_expert` (False),
`norm_topk` (False: softmax over ALL the logits, the top_k kept as they
are), `act` ("none": the SiLU gate dropped, `down(up y)`), `held_term`
(False: the routed experts' term dropped), `state_dtype` ("bfloat16"),
`stale_state` (n) and `pad_tail` (n, with `prompt_len`) as
`nemotron_h_ref.py` has them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512       # query rows a block of attention


def blocks(pattern: str) -> str:
    return "".join(kind + "E" for kind in pattern)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(lp, y, model, prompt_len=None):
    """The Mamba-2 mixer for the tokens y [T, H], token by token."""
    T = y.shape[0]
    nh, P = model["ssm_heads"], model["ssm_head_dim"]
    G, N, K = model["ssm_groups"], model["ssm_state"], model["conv_kernel"]
    inner, gn = nh * P, G * N
    eps = model.get("rms_eps", 1e-5)
    zxd = y @ lp["blk.in_proj"]
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn],
                  zxd[:, 2 * inner + 2 * gn:])
    if model.get("dt_bias", True):
        dt = dt + lp["blk.dt_bias"]
    dt = jax.nn.softplus(dt)
    A = -jnp.exp(lp["blk.A_log"])
    D = jnp.zeros_like(lp["blk.D"]) if model.get("skip_D") else lp["blk.D"]
    bias = lp["blk.conv_b"] if model.get("conv_bias", True) else 0.0
    sdt = jnp.dtype(model.get("state_dtype", "float32"))
    per_head = bool(model.get("bc_per_head"))

    def of_heads(m, turn=1):        # [G, N] -> [nh, N]
        m = jnp.repeat(m, nh // G, axis=0)
        if per_head:    # B by h lanes, C by 2h: the same turn would cancel
            m = jax.vmap(jnp.roll)(m, turn * jnp.arange(nh))
        return m

    def token(carry, t):
        window, S = carry                   # [K, C] newest last; [nh, P, N]
        u, dt_t = t
        window = jnp.concatenate([window[1:], u[None]], axis=0)
        c = _silu(jnp.sum(window * lp["blk.conv_w"], axis=0) + bias)
        x = c[:inner].reshape(nh, P)
        B = of_heads(c[inner:inner + gn].reshape(G, N))
        C = of_heads(c[inner + gn:].reshape(G, N), 2)
        S = jnp.exp(dt_t * A)[:, None, None] * S.astype(jnp.float32) \
            + (dt_t[:, None] * x)[:, :, None] * B[:, None, :]
        S = S.astype(sdt)
        out = jnp.sum(S.astype(jnp.float32) * C[:, None, :], axis=-1) \
            + D[:, None] * x
        return (window, S), out.reshape(inner)

    carry = (jnp.zeros((K, xbc.shape[1]), jnp.float32),
             jnp.zeros((nh, P, N), sdt))
    stale = int(model.get("stale_state", 0))
    if stale:       # the row's last holder: n copies of the first input
        carry, _ = jax.lax.scan(
            token, carry, (jnp.broadcast_to(xbc[0], (stale,) + xbc.shape[1:]),
                           jnp.broadcast_to(dt[0], (stale,) + dt.shape[1:])))
    pad = int(model.get("pad_tail", 0))
    if pad and prompt_len is not None:
        # the prompt, then `pad` more copies of its last token that a
        # faulty prefill lets count, then the generated tokens
        at = jnp.arange(T + pad)
        order = jnp.where(at < prompt_len, at,
                          jnp.where(at < prompt_len + pad, prompt_len - 1,
                                    at - pad))
        _, ys = jax.lax.scan(token, carry, (xbc[order], dt[order]))
        keep = jnp.where(jnp.arange(T) < prompt_len, jnp.arange(T),
                         jnp.arange(T) + pad)
        ys = ys[keep]
    else:
        _, ys = jax.lax.scan(token, carry, (xbc, dt))
    ys = ys * _silu(z)
    groups = int(model.get("norm_groups", G))
    g = ys.reshape(T, groups, inner // groups)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(T, inner) * lp["blk.gnorm.scale"]) @ lp["blk.out_proj"]


def route(logits, model):
    """[T, E] weights, zero off a token's top_k: the published order, the
    top_k largest LOGITS and a softmax over them."""
    k = model["top_k"]
    if model.get("norm_topk", True):
        kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
        kept = logits >= kth
        e = jnp.where(kept, jnp.exp(logits - logits.max(-1, keepdims=True)),
                      0.0)
        return e / e.sum(-1, keepdims=True)
    s = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(s, axis=-1)[:, -k][:, None]
    return jnp.where(s >= kth, s, 0.0)


def _mlp(y, gate, up, down, model):
    if model.get("act", "silu") == "none":
        return (y @ up) @ down
    return (_silu(y @ gate) * (y @ up)) @ down


def experts(lp, y, model):
    """sum_e w_e expert_e(y) over each token's chosen experts that are
    HELD, plus the shared expert; y [T, H]."""
    E = model["n_experts"]
    first, past = model.get("held") or (0, E)
    w = route(y @ lp["blk.router"], model)
    # the stacks hold the held experts alone, or all of them
    offset = first if lp["blk.w_gate"].shape[0] == past - first else 0

    def one(e, acc):
        out = _mlp(y, lp["blk.w_gate"][e - offset], lp["blk.w_up"][e - offset],
                   lp["blk.w_down"][e - offset], model)
        return acc + w[:, e][:, None] * out

    out = jnp.zeros_like(y)
    if model.get("held_term", True):
        out = jax.lax.fori_loop(first, past, one, out)
    if model.get("shared_expert", True):
        out = out + _mlp(y, lp["blk.shared_gate"], lp["blk.shared_up"],
                         lp["blk.shared_down"], model)
    return out


def _rope_half(x, theta=10000.0):
    """x [T, heads, d] at positions 0..T-1, rotate-half over all of d: a
    fault the published attention does not have."""
    T, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(lp, y, model):
    """Causal grouped-query attention, a block of query rows at a time."""
    T = y.shape[0]
    nh, kvh, d = model["heads"], model["kv_heads"], model["head_dim"]
    q = (y @ lp["blk.wq"]).reshape(T, nh, d)
    k = (y @ lp["blk.wk"]).reshape(T, kvh, d)
    v = (y @ lp["blk.wv"]).reshape(T, kvh, d)
    if model.get("rope", False):
        q, k = _rope_half(q), _rope_half(k)
    k = jnp.repeat(k, nh // kvh, axis=1)    # query head h reads h // group
    v = jnp.repeat(v, nh // kvh, axis=1)
    out = []
    for first in range(0, T, Q_BLOCK):
        rows = slice(first, min(first + Q_BLOCK, T))
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) \
            * model["attention_multiplier"]
        seen = jnp.arange(T)[None, :] <= jnp.arange(T)[rows, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0).reshape(T, -1) @ lp["blk.wo"]


def block(lp, x, model, kind, prompt_len=None):
    """One block of `kind` for the tokens x [T, H] at positions 0..T-1."""
    y = _rms(x, lp["blk.norm.scale"], model.get("rms_eps", 1e-5))
    if kind == "M":
        out = _mamba(lp, y, model, prompt_len)
    elif kind == "E":
        out = experts(lp, y, model)
    elif kind == "*":
        out = _attention(lp, y, model)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return x + model["residual_multiplier"] * out


PREFIX = {"M": "mamba.", "E": "moe.", "*": "attn."}


def layer_of(params, model, b):
    """Block b's parameters out of the program's flat set (the blocks of a
    kind stacked under the kind's prefix, in the pattern's order)."""
    order = blocks(model["pattern"])
    kind = order[b]
    nth = order[:b].count(kind)
    prefix = PREFIX[kind]
    return {"blk." + k[len(prefix):]: v[nth] for k, v in params.items()
            if k.startswith(prefix)}


def embed(params, model, ids):
    return model["embedding_multiplier"] * params["wte.w"][ids]


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-5))
    return rows @ params["wte.w"].T / model["logits_scaling"]


def logits_rows(params, model, ids, first, n_rows, prompt_len=None):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every block (stacked); row t
    predicts token t + 1."""
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
    routed experts are held, and the router's 10th and 11th logits lie
    close, so rounding to bf16 flips a row's last pick now and then; where
    the flip crosses the edge of the held range that token gains or loses a
    whole expert's term, one token of the fault "held term dropped" itself.
    What tells rounding from a fault is HOW MANY tokens move."""
    gaps = np.sort(np.asarray(gaps, np.float64))
    rest = gaps[:len(gaps) - spared(len(gaps))]
    return float(max(rest.max(), MEAN_TIMES * rest.mean()))


def stream_rows(top, layer, model, prompts, streams, width, weights=None):
    """The float32 logits `[len(stream), vocab]` that predict each stream's
    tokens after its prompt, teacher-forced. `top` holds the parameters
    outside the layers, `layer(b)` gives block b's in float32: the
    sequences go through one block at a time, and only that block's weights
    need to exist. Rows are padded to the longest stream's length (at most
    `width`), rounded up to 128, so that one program a block kind serves
    every stream; a causal model keeps the padding out of every row that is
    read. `weights(name, value)` is a control on the parameters (rounding
    them to a lower precision), applied a tensor at a time."""
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, jnp.asarray(v, jnp.float32))
           for k, v in top.items()}
    n_new = len(streams[0])
    longest = max(len(p) for p in prompts) + n_new
    width = min(int(width), -(-longest // 128) * 128)
    order = blocks(model["pattern"])
    steps = {kind: jax.jit(
        lambda lp, x, n, kind=kind: block(lp, x, model, kind, n))
        for kind in set(order)}
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
            xs = [steps[kind](lp, x, np.int32(len(p)))
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
