"""Plain float32 OLMoE forward pass, written from the published description
(Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts Language Models",
and the model's `config.json`): token embedding -> pre-norm blocks -> final
RMSNorm -> an output head of its own. One block:

    y = RMSNorm(x; w_in)
    q = RMSNorm(y Wq; w_qn), k = RMSNorm(y Wk; w_kn), v = y Wv   (no biases;
        QK-norm over the whole projection, before the split into heads)
    q, k <- RoPE at the token's position (theta, rotate-half, whole head dim)
    h = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
    y = RMSNorm(h; w_post)
    p = softmax(y Wg) over all experts; the top_k largest p_e are kept as they
        are (`norm_topk_prob` false: NOT renormalised)
    out = h + sum over the kept e of p_e * (silu(y G_e) * (y U_e)) D_e

Straightforward jax.numpy, one unbatched row of tokens at a time: no KV
cache, no engine, no kernel, no sorting or grouping of tokens, no code of
the program. Every expert is computed for every token and masked by the
router's choice (a plain loop over all of them). Parameters are the
program's flat dict; a layer's are passed unstacked (`layer_of` slices a
stacked set), so that a model whose float32 set does not fit the device
can be walked one layer at a time (`stream_gaps`).

The switches `norm_topk_prob`, `qk_norm`, `rope_q_offset` (queries rotated
as if that many positions later: RoPE is relative, so a shift of q and k
together changes nothing) and `drop_expert_rank` (the kept expert of that
rank, 0 the likeliest, left out) of `model` exist for the tests that show
what the comparison tells apart; their defaults are the published model."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [T, heads, head_dim], pos [T]: pairs (i, i + head_dim/2) rotated by
    pos * theta^(-2i/head_dim)."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]       # [T, d/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]       # [T, 1, d]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _experts(lp, y, model):
    """sum_e p_e * expert_e(y) over each token's chosen experts; y [T, H]."""
    k = model["top_k"]
    probs = jax.nn.softmax(y @ lp["blk.router"], axis=-1)        # [T, E]
    kth = jnp.sort(probs, axis=-1)[:, -k][:, None]
    keep = probs >= kth                                          # top k
    p = jnp.where(keep, probs, 0.0)
    if "drop_expert_rank" in model:     # a test's fault: one kept expert out
        r = jnp.sort(p, axis=-1)[:, -1 - model["drop_expert_rank"]][:, None]
        p = jnp.where(p == r, 0.0, p)
    if model.get("norm_topk_prob", False):
        p = p / p.sum(-1, keepdims=True)

    def one(e, acc):
        g = y @ lp["blk.w_gate"][e]
        u = y @ lp["blk.w_up"][e]
        out = (g * jax.nn.sigmoid(g) * u) @ lp["blk.w_down"][e]
        return acc + p[:, e][:, None] * out

    return jax.lax.fori_loop(0, probs.shape[-1], one, jnp.zeros_like(y))


def block(lp, x, model):
    """One block for the tokens x [T, H] at positions 0..T-1."""
    T, H = x.shape
    nh = model["heads"]
    hd = H // nh
    eps = model.get("rms_eps", 1e-5)
    y = _rms(x, lp["blk.ln_in.scale"], eps)
    q, k, v = y @ lp["blk.wq"], y @ lp["blk.wk"], y @ lp["blk.wv"]
    if model.get("qk_norm", True):
        q = _rms(q, lp["blk.q_norm.scale"], eps)
        k = _rms(k, lp["blk.k_norm.scale"], eps)
    pos = jnp.arange(T)
    theta = model.get("rope_theta", 10000.0)
    q = _rope(q.reshape(T, nh, hd), pos + model.get("rope_q_offset", 0),
              theta)
    k = _rope(k.reshape(T, nh, hd), pos, theta)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                     v.reshape(T, nh, hd))
    h = x + ctx.reshape(T, H) @ lp["blk.wo"]
    y = _rms(h, lp["blk.ln_post.scale"], eps)
    return h + _experts(lp, y, model)


def layer_of(params, i):
    """Layer i's parameters out of a set stacked on a leading axis."""
    return {k: v[i] for k, v in params.items() if k.startswith("blk.")}


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-5))
    return rows @ params["head.w"]


def logits_rows(params, model, ids, first, n_rows):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every layer (stacked); row t
    predicts token t + 1."""
    x = params["wte.w"][ids]
    for i in range(model["layers"]):
        x = block(layer_of(params, i), x, model)
    return head_rows(params, model, x, first, n_rows)


def stream_gaps(top, layer, model, prompts, streams, width):
    """For each (prompt, generated tokens): how far, in float32 logits, each
    generated token lies below the reference's own argmax at its position,
    teacher-forced. Returns (largest gap, tokens equal to the argmax).
    `top` holds the parameters outside the layers, `layer(i)` gives layer
    i's in float32: the sequences go through one layer at a time, and only
    that layer's weights need to exist. Rows are padded to `width` so that
    one program serves every stream; causal attention keeps the padding out
    of every row that is read."""
    top = {k: jnp.asarray(v, jnp.float32) for k, v in top.items()}
    n_new = len(streams[0])
    step = jax.jit(lambda lp, x: block(lp, x, model))
    head = jax.jit(lambda p, x, first: head_rows(p, model, x, first, n_new))
    gaps, exact = [], 0
    with jax.default_matmul_precision("highest"):
        xs = []
        for prompt, generated in zip(prompts, streams):
            ids = np.zeros((width,), np.int32)
            ids[:len(prompt) + n_new] = list(prompt) + list(generated)
            xs.append(top["wte.w"][jnp.asarray(ids)])
        for i in range(model["layers"]):
            lp = {k: jnp.asarray(v, jnp.float32)
                  for k, v in layer(i).items()}
            xs = [step(lp, x) for x in xs]
            del lp
        for x, prompt, generated in zip(xs, prompts, streams):
            rows = np.asarray(head(top, x, np.int32(len(prompt) - 1)),
                              np.float32)
            picked = rows[np.arange(n_new), generated]
            gaps.append(float((rows.max(axis=-1) - picked).max()))
            exact += int((rows.argmax(axis=-1) == generated).sum())
    return max(gaps), exact
