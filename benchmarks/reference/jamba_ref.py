"""Plain float32 Jamba forward pass, written from the model's `config.json`
(ai21labs/AI21-Jamba2-3B, `model_type` `jamba`) and the published `jamba`
modelling code: token embedding -> blocks -> final RMSNorm -> the embedding
read as the output head (tied). A block is `h + f(RMSNorm(h))` (eps 1e-6),
`f` by the block's character in `pattern`:

    M   [x | z] = y W_in                        hidden -> inner | inner
        x = silu(conv(x) + b_conv)              depthwise, causal, K taps
        [r | B | C] = x W_x                     inner -> R | N | N
        r, B, C = RMSNorm_dt(r), RMSNorm_B(B), RMSNorm_C(C)
        dt = softplus(r W_dt + b_dt);  A = -exp(A_log)
        S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
        y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] x_t[c],  S_0 = 0
        out = (y * silu(z)) W_out
    *   q = y W_q -> heads x D; k, v = y W_k, y W_v -> ONE head of D; every
        query head reads it; causal softmax at 1/sqrt(D); NO position
        enters; out = ctx W_o
    E   out = W_d (silu(W_g y) * W_u y)

A published layer is a mixer then a feed-forward, each behind a norm of its
own: two blocks here, `ME` or `*E`. The recurrence runs TOKEN BY TOKEN (a
`lax.scan` over the sequence, the convolution's window in its carry): no
chunks, no cache, no state pool, no code of the program. One unbatched row
of tokens at a time; a block's parameters are passed unstacked under the
prefix `blk.`.

Departures from the published code, each without effect on a value: `A_log`
is held `[N, inner]` (published `[inner, N]`: the same numbers transposed);
the three inner norms are always on (the published code applies them
whenever the layer has them, and the `jamba` configs all do).

The switches of `model` exist for the tests and readings that show what
the comparison tells apart; their defaults are the published model:
`state_dtype` ("bfloat16": the state rounded after every token),
`scalar_decay` (True: a channel's 16 lanes decay alike, at their mean `A`),
`dt_norm` / `b_norm` / `c_norm` (False: that inner norm left out),
`conv_bias` (False), `dt_bias` (False), `skip_D` (True), `rope` (True:
rotary positions on q and k, rotate-half, theta 10000), `learned_pos`
(True: a sinusoidal position table added to the attention blocks' input),
`pad_tail` (n: after the prompt's last token, n more copies of it count
before the generated tokens come, what a padded bucket does if the padding
advances the state; needs `prompt_len`), `pad_conv` (n: the same copies
move the convolution's window alone, the state standing still: the tail
taken from the bucket's end and not at the prompt's length)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512       # query rows a block of attention


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _inner_norm(x, gain, model, switch):
    return _rms(x, gain, model.get("rms_eps", 1e-6)) \
        if model.get(switch, True) else x


def _mamba(lp, y, model, prompt_len=None):
    """The Mamba-1 mixer for the tokens y [T, H], token by token."""
    T = y.shape[0]
    C = model["expand"] * model["hidden"]
    N, R, K = model["ssm_state"], model["dt_rank"], model["conv_kernel"]
    xz = y @ lp["blk.in_proj"]
    u, z = xz[:, :C], xz[:, C:]
    A = -jnp.exp(lp["blk.A_log"])                               # [N, C]
    if model.get("scalar_decay"):
        A = jnp.broadcast_to(A.mean(axis=0, keepdims=True), A.shape)
    D = jnp.zeros_like(lp["blk.D"]) if model.get("skip_D") else lp["blk.D"]
    bias = lp["blk.conv_b"] if model.get("conv_bias", True) else 0.0
    dt_bias = lp["blk.dt_bias"] if model.get("dt_bias", True) else 0.0
    sdt = jnp.dtype(model.get("state_dtype", "float32"))

    def token(carry, t):
        window, S = carry                   # [K, C] newest last; [N, C]
        u_t, counts = t
        window = jnp.concatenate([window[1:], u_t[None]], axis=0)
        x = _silu(jnp.sum(window * lp["blk.conv_w"], axis=0) + bias)
        rbc = x @ lp["blk.x_proj"]
        r = _inner_norm(rbc[:R], lp["blk.dt_norm"], model, "dt_norm")
        B = _inner_norm(rbc[R:R + N], lp["blk.b_norm"], model, "b_norm")
        Cv = _inner_norm(rbc[R + N:], lp["blk.c_norm"], model, "c_norm")
        dt = jax.nn.softplus(r @ lp["blk.dt_proj"] + dt_bias) * counts
        S = jnp.exp(dt[None, :] * A) * S.astype(jnp.float32) \
            + (dt * x)[None, :] * B[:, None]
        S = S.astype(sdt)
        out = jnp.sum(S.astype(jnp.float32) * Cv[:, None], axis=0) + D * x
        return (window, S), out

    carry = (jnp.zeros((K, C), jnp.float32), jnp.zeros((N, C), sdt))
    pad_tail = int(model.get("pad_tail", 0))
    pad_conv = int(model.get("pad_conv", 0))
    pad = pad_tail or pad_conv
    if pad and prompt_len is not None:
        # the prompt, then `pad` more copies of its last token that a
        # faulty prefill lets count (`pad_tail`) or lets through the
        # convolution's window alone (`pad_conv`), then the generated ones
        at = jnp.arange(T + pad)
        padding = (at >= prompt_len) & (at < prompt_len + pad)
        order = jnp.where(at < prompt_len, at,
                          jnp.where(padding, prompt_len - 1, at - pad))
        counts = jnp.where(padding & (pad_tail == 0), 0.0, 1.0)
        _, ys = jax.lax.scan(token, carry, (u[order], counts))
        keep = jnp.where(jnp.arange(T) < prompt_len, jnp.arange(T),
                         jnp.arange(T) + pad)
        ys = ys[keep]
    else:
        _, ys = jax.lax.scan(token, carry, (u, jnp.ones((T,), jnp.float32)))
    return (ys * _silu(z)) @ lp["blk.out_proj"]


def _rope_half(x, theta=10000.0):
    """x [T, heads, d] at positions 0..T-1, rotate-half over all of d: a
    fault the published attention does not have."""
    T, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _positions(T, H):
    """A sinusoidal position table [T, H]: a fault the model does not
    have."""
    freq = 1.0 / 10000.0 ** (jnp.arange(0, H, 2, dtype=jnp.float32) / H)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _attention(lp, y, model):
    """Causal multi-query attention, a block of query rows at a time."""
    T = y.shape[0]
    nh, d = model["heads"], model["head_dim"]
    if model.get("learned_pos"):
        y = y + _positions(T, y.shape[1])
    q = (y @ lp["blk.wq"]).reshape(T, nh, d)
    k = (y @ lp["blk.wk"]).reshape(T, 1, d)
    v = (y @ lp["blk.wv"]).reshape(T, d)
    if model.get("rope", False):
        q, k = _rope_half(q), _rope_half(k)
    k = k[:, 0]
    out = []
    for first in range(0, T, Q_BLOCK):
        rows = slice(first, min(first + Q_BLOCK, T))
        s = jnp.einsum("qhd,kd->hqk", q[rows], k) / math.sqrt(d)
        seen = jnp.arange(T)[None, :] <= jnp.arange(T)[rows, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0).reshape(T, -1) @ lp["blk.wo"]


def _swiglu(lp, y):
    return (_silu(y @ lp["blk.w_gate"]) * (y @ lp["blk.w_up"])) \
        @ lp["blk.w_down"]


def block(lp, x, model, kind, prompt_len=None):
    """One block of `kind` for the tokens x [T, H] at positions 0..T-1."""
    y = _rms(x, lp["blk.norm.scale"], model.get("rms_eps", 1e-6))
    if kind == "M":
        return x + _mamba(lp, y, model, prompt_len)
    if kind == "E":
        return x + _swiglu(lp, y)
    if kind == "*":
        return x + _attention(lp, y, model)
    raise ValueError(f"unknown block kind {kind!r}")


PREFIX = {"M": "mamba.", "E": "mlp.", "*": "attn."}


def pattern_of(model) -> str:
    """A character a block: layer l is `*E` where `l % attn_period ==
    attn_offset`, `ME` elsewhere."""
    return "".join(
        "*E" if l % model["attn_period"] == model["attn_offset"] else "ME"
        for l in range(model["n_layers"]))


def layer_of(params, model, i):
    """Block i's parameters out of the program's flat set (the blocks of a
    kind stacked under the kind's prefix, in the pattern's order)."""
    pattern = pattern_of(model)
    kind = pattern[i]
    nth = pattern[:i].count(kind)
    prefix = PREFIX[kind]
    return {"blk." + k[len(prefix):]: v[nth] for k, v in params.items()
            if k.startswith(prefix)}


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-6))
    return rows @ params["wte.w"].T


def logits_rows(params, model, ids, first, n_rows, prompt_len=None):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every block (stacked); row t
    predicts token t + 1."""
    x = params["wte.w"][ids]
    for i, kind in enumerate(pattern_of(model)):
        x = block(layer_of(params, model, i), x, model, kind, prompt_len)
    return head_rows(params, model, x, first, n_rows)


MEAN_TIMES = 16     # the mean's weight beside the worst token (`verdict`)


def verdict(gaps) -> float:
    """One number of the sampled tokens' gaps for the tolerance: the WORST
    token's, or `MEAN_TIMES` the MEAN over the tokens where that is larger
    (`joyai_ref.verdict` says why both)."""
    gaps = np.asarray(gaps, np.float64)
    return float(max(gaps.max(), MEAN_TIMES * gaps.mean()))


def stream_rows(top, layer, model, prompts, streams, width, weights=None):
    """For each (prompt, generated tokens) the reference's float32 logits
    `[tokens, vocab]` at the generated tokens' positions, teacher-forced.
    `top` holds the parameters outside the blocks, `layer(i)` gives block
    i's in float32: the sequences go through one block at a time, and only
    that block's weights need to exist. Rows are padded to the longest
    stream's length (at most `width`), rounded up to 128, so that one
    program a block kind serves every stream; a causal model keeps the
    padding out of every row that is read. `weights(name, value)`: a
    control on the parameters (a lower precision), for the readings."""
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, jnp.asarray(v, jnp.float32))
           for k, v in top.items()}
    pattern = pattern_of(model)
    n_new = len(streams[0])
    longest = max(len(p) for p in prompts) + n_new
    width = min(int(width), -(-longest // 128) * 128)
    steps = {kind: jax.jit(
        lambda lp, x, n, kind=kind: block(lp, x, model, kind, n))
        for kind in set(pattern)}
    head = jax.jit(lambda p, x, first: head_rows(p, model, x, first, n_new))
    with jax.default_matmul_precision("highest"):
        xs = []
        for prompt, generated in zip(prompts, streams):
            ids = np.zeros((width,), np.int32)
            ids[:len(prompt) + n_new] = list(prompt) + list(generated)
            xs.append(top["wte.w"][jnp.asarray(ids)])
        for i, kind in enumerate(pattern):
            lp = {k: weights(k, jnp.asarray(v, jnp.float32))
                  for k, v in layer(i).items()}
            xs = [steps[kind](lp, x, np.int32(len(p)))
                  for x, p in zip(xs, prompts)]
            del lp
        return [np.asarray(head(top, x, np.int32(len(prompt) - 1)),
                           np.float32) for x, prompt in zip(xs, prompts)]


def gaps_of(rows, picks):
    """How far, in the logits `rows` (one `[tokens, vocab]` a stream), each
    picked token lies below the row's own argmax."""
    return np.concatenate([r.max(axis=-1) - r[np.arange(len(p)), p]
                           for r, p in zip(rows, picks)])


def stream_gaps(top, layer, model, prompts, streams, width):
    """(`verdict` of the gaps of every generated token under the
    reference's logits at its position, tokens equal to the argmax): what
    the serve kind compares with `logit_gap_tol`."""
    rows = stream_rows(top, layer, model, prompts, streams, width)
    exact = sum(int((r.argmax(axis=-1) == np.asarray(s)).sum())
                for r, s in zip(rows, streams))
    return verdict(gaps_of(rows, streams)), exact
