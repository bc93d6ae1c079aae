"""Plain float32 Nemotron-H forward pass, written from the model's
`config.json` (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, `model_type`
`nemotron_h`): token embedding -> blocks of ONE mixer each -> final RMSNorm
-> an output head of its own. Every block is `h + mixer(RMSNorm(h))` (eps
1e-5, no bias but the convolution's), and the mixer's kind is the block's
character in `pattern`:

    M   [z | xBC | dt] = y W_in              inner | inner + 2 G N | heads
        xBC = silu(conv(xBC) + b)            depthwise, causal, 4 taps
        [x | B | C] = xBC                    heads x P | G x N | G x N
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
            a head at a time, B and C of the head's group, S_0 = 0
        out = (RMSNorm over groups of inner / G of (y * silu(z)) * w) W_out
    E   s = sigmoid(y W_r); the top_k of (s + b) are chosen, b the
        correction bias; w_e = s_e / (sum of the chosen s_e + 1e-20) x 2.5
        out = sum_e w_e D_e relu(U_e y)^2 + D_s relu(U_s y)^2
    *   q = y W_q -> heads x 128; k, v = y W_k, y W_v -> kv_heads x 128;
        query head h reads K/V head h // (heads / kv_heads); causal
        softmax at 1/sqrt(128); NO position enters; out = ctx W_o

The recurrence runs TOKEN BY TOKEN (a `lax.scan` over the sequence), the
experts as a plain loop over all of them with the router's choice as a
mask: no chunks, no cache, no state pool, no sorting or grouping of tokens,
no code of the program. One unbatched row of tokens at a time; a block's
parameters are passed unstacked under the prefix `blk.`, the routed
experts at their published width.

The switches of `model` exist for the tests and readings that show what
the comparison tells apart; their defaults are the published model:
`state_dtype` ("bfloat16": the SSM state rounded after every token),
`conv_bias` (False: left out), `skip_D` (True), `norm_groups` (1: one norm
over all of inner), `dt_bias` (False), `act` ("relu" | "silu" for relu^2),
`shared_expert` (False), `route_scale` (1.0: left out), `rope` (True:
rotary positions on q and k, rotate-half, theta 10000), `stale_state`
(n: every Mamba layer starts from the state n copies of the prompt's first
input leave, a row not reset at admission), `pad_tail` (n: after the
prompt's last token the state is advanced by n more copies of it before
the generated tokens come, what a padded bucket does if the padding
counts; needs `prompt_len`)."""

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

    def token(carry, t):
        window, S = carry                   # [K, C] newest last; [nh, P, N]
        u, dt_t = t
        window = jnp.concatenate([window[1:], u[None]], axis=0)
        c = _silu(jnp.sum(window * lp["blk.conv_w"], axis=0) + bias)
        x = c[:inner].reshape(nh, P)
        B = jnp.repeat(c[inner:inner + gn].reshape(G, N), nh // G, axis=0)
        C = jnp.repeat(c[inner + gn:].reshape(G, N), nh // G, axis=0)
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


def _act(x, model):
    kind = model.get("act", "relu2")
    if kind == "relu2":
        return jnp.square(jnp.maximum(x, 0.0))
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    return _silu(x)


def _experts(lp, y, model):
    """sum_e w_e * expert_e(y) over each token's chosen experts, plus the
    shared expert; y [T, H]."""
    k = model["top_k"]
    s = jax.nn.sigmoid(y @ lp["blk.router"])
    biased = s + lp["blk.router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -k][:, None]
    w = jnp.where(biased >= kth, s, 0.0)                    # top k
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * model.get("route_scale", 2.5)

    def one(e, acc):
        out = _act(y @ lp["blk.w_up"][e], model) @ lp["blk.w_down"][e]
        return acc + w[:, e][:, None] * out

    out = jax.lax.fori_loop(0, s.shape[-1], one, jnp.zeros_like(y))
    if model.get("shared_expert", True):
        out = out + _act(y @ lp["blk.shared_up"], model) \
            @ lp["blk.shared_down"]
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
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(d)
        seen = jnp.arange(T)[None, :] <= jnp.arange(T)[rows, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0).reshape(T, -1) @ lp["blk.wo"]


def block(lp, x, model, kind, prompt_len=None):
    """One block of `kind` for the tokens x [T, H] at positions 0..T-1."""
    y = _rms(x, lp["blk.norm.scale"], model.get("rms_eps", 1e-5))
    if kind == "M":
        return x + _mamba(lp, y, model, prompt_len)
    if kind == "E":
        return x + _experts(lp, y, model)
    if kind == "*":
        return x + _attention(lp, y, model)
    raise ValueError(f"unknown block kind {kind!r}")


PREFIX = {"M": "mamba.", "E": "moe.", "*": "attn."}


def layer_of(params, model, i):
    """Block i's parameters out of the program's flat set (the blocks of a
    kind stacked under the kind's prefix, in the pattern's order), the
    routed experts cut back to their published width."""
    kind = model["pattern"][i]
    nth = model["pattern"][:i].count(kind)
    prefix = PREFIX[kind]
    lp = {"blk." + k[len(prefix):]: v[nth] for k, v in params.items()
          if k.startswith(prefix)}
    if kind == "E":
        m = model["expert_dim"]
        lp["blk.w_up"] = lp["blk.w_up"][:, :, :m]
        lp["blk.w_down"] = lp["blk.w_down"][:, :m]
    return lp


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-5))
    return rows @ params["head.w"]


def logits_rows(params, model, ids, first, n_rows, prompt_len=None):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every block (stacked); row t
    predicts token t + 1."""
    x = params["wte.w"][ids]
    for i, kind in enumerate(model["pattern"]):
        x = block(layer_of(params, model, i), x, model, kind, prompt_len)
    return head_rows(params, model, x, first, n_rows)


MEAN_TIMES = 16     # the mean's weight beside the worst token (`verdict`)


def verdict(gaps) -> float:
    """One number of the sampled tokens' gaps for the tolerance: the WORST
    token's, or `MEAN_TIMES` the MEAN over the tokens where that is larger
    (`joyai_ref.verdict` says why both)."""
    gaps = np.asarray(gaps, np.float64)
    return float(max(gaps.max(), MEAN_TIMES * gaps.mean()))


def stream_gaps(top, layer, model, prompts, streams, width):
    """For each (prompt, generated tokens): how far, in float32 logits, each
    generated token lies below the reference's own argmax at its position,
    teacher-forced. Returns (`verdict` of all the gaps, tokens equal to the
    argmax). `top` holds the parameters outside the blocks, `layer(i)`
    gives block i's in float32: the sequences go through one block at a
    time, and only that block's weights need to exist. Rows are padded to
    the longest stream's length (at most `width`), rounded up to 128, so
    that one program a block kind serves every stream; a causal model
    keeps the padding out of every row that is read."""
    top = {k: jnp.asarray(v, jnp.float32) for k, v in top.items()}
    n_new = len(streams[0])
    longest = max(len(p) for p in prompts) + n_new
    width = min(int(width), -(-longest // 128) * 128)
    steps = {kind: jax.jit(
        lambda lp, x, n, kind=kind: block(lp, x, model, kind, n))
        for kind in set(model["pattern"])}
    head = jax.jit(lambda p, x, first: head_rows(p, model, x, first, n_new))
    gaps, exact = [], 0
    with jax.default_matmul_precision("highest"):
        xs = []
        for prompt, generated in zip(prompts, streams):
            ids = np.zeros((width,), np.int32)
            ids[:len(prompt) + n_new] = list(prompt) + list(generated)
            xs.append(top["wte.w"][jnp.asarray(ids)])
        for i, kind in enumerate(model["pattern"]):
            lp = {k: jnp.asarray(v, jnp.float32)
                  for k, v in layer(i).items()}
            xs = [steps[kind](lp, x, np.int32(len(p)))
                  for x, p in zip(xs, prompts)]
            del lp
        for x, prompt, generated in zip(xs, prompts, streams):
            rows = np.asarray(head(top, x, np.int32(len(prompt) - 1)),
                              np.float32)
            picked = rows[np.arange(n_new), generated]
            gaps.extend(rows.max(axis=-1) - picked)
            exact += int((rows.argmax(axis=-1) == generated).sum())
    return verdict(gaps), exact
