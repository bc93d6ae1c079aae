"""Plain float32 JoyAI-LLM-Flash forward pass, written from the model's
`config.json` (jdopensource/JoyAI-LLM-Flash; the layer is DeepSeek-V3's
with one group): token embedding -> pre-norm blocks -> final RMSNorm -> an
output head of its own. One block (eps 1e-6, no biases):

    y        = RMSNorm(x)
    c_q      = RMSNorm(y W_qa);  q_h = c_q W_qb -> heads x [nope 128 | rope 64]
    [c | kr] = y W_kva;  c = RMSNorm(c)            [512 | 64]
    q_rope_h, kr <- RoPE at the token's position: interleaved pairs
        (2i, 2i+1), theta^(-2i/64); ONE rotary key for all heads
    [k_nope_h | v_h] = c W_kvb -> heads x [128 | 128]
    h = x + concat_h softmax((q_nope_h . k_nope_h + q_rope_h . kr)
                             / sqrt(192), causal) v_h  W_o
    y = RMSNorm(h)
    a dense layer (its parameters hold `blk.mlp_gate`):
        out = h + (silu(y G) * (y U)) D
    an expert layer: s = sigmoid(y W_r); the top_k of (s + b) are chosen, b
        the correction bias; w_e = s_e / (sum of the chosen s_e + 1e-20)
        * route_scale; out = h + sum_e w_e SwiGLU_e(y) + SwiGLU_shared(y)

The attention here is the EXPANDED form: every head's keys and values are
made from `c` and attended as ordinary multi-head attention. The program
serves the absorbed form through a latent cache (models/joyai.py); that the
two agree is what the comparison checks. Straightforward jax.numpy, one
unbatched row of tokens at a time: no cache, no engine, no kernel, no
sorting or grouping of tokens, no code of the program. Every expert is
computed for every token and masked by the router's choice (a plain loop
over all of them); attention goes in blocks of query rows so that a
4608-token sequence's scores fit beside one float32 expert layer. A
layer's parameters are passed unstacked under the prefix `blk.`.

The switches of `model` exist for the tests that show what the comparison
tells apart; their defaults are the published model: `shared_expert`
(False: left out), `route_scale` (1.0: left out), `score` ("softmax"),
`bias_selects` (False: the top-k of the scores alone), `bias_weighs`
(True: the weights taken of s + b), `norm_topk_prob` (False), `rope`
("half": pairs (i, i + 32)), `rope_on` ("nope": the first 64 lanes of the
no-position part rotated instead), `kv_norm` (False: `c` used, as a cache
would store it, before its norm), `drop_pair` (True: the least of a
token's chosen experts contributes nothing and the others' weights stay,
what a capacity limit does to a token)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512       # query rows a block of attention


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta, convention="interleaved"):
    """x [T, ..., d], pos [T]: pair i turns by pos * theta^(-2i/d); the pair
    is lanes (2i, 2i+1) ("interleaved") or (i, i + d/2) ("half")."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]       # [T, d/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if convention == "half":
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(y, gate, up, down):
    g = y @ gate
    return (g * jax.nn.sigmoid(g) * (y @ up)) @ down


def _experts(lp, y, model):
    """sum_e w_e * expert_e(y) over each token's chosen experts, plus the
    shared expert; y [T, H]."""
    k = model["top_k"]
    logits = y @ lp["blk.router"]
    if model.get("score", "sigmoid") == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    biased = s + lp["blk.router_bias"]
    chooser = biased if model.get("bias_selects", True) else s
    kth = jnp.sort(chooser, axis=-1)[:, -k][:, None]
    keep = chooser >= kth                                        # top k
    w = jnp.where(keep, biased if model.get("bias_weighs", False) else s,
                  0.0)
    if model.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * model.get("route_scale", 2.5)
    if model.get("drop_pair", False):   # a token's least chosen expert lost
        least = jnp.where(keep, w, jnp.inf).min(-1, keepdims=True)
        w = jnp.where(w == least, 0.0, w)

    def one(e, acc):
        out = _swiglu(y, lp["blk.w_gate"][e], lp["blk.w_up"][e],
                      lp["blk.w_down"][e])
        return acc + w[:, e][:, None] * out

    out = jax.lax.fori_loop(0, s.shape[-1], one, jnp.zeros_like(y))
    if model.get("shared_expert", True):
        out = out + _swiglu(y, lp["blk.shared_gate"], lp["blk.shared_up"],
                            lp["blk.shared_down"])
    return out


def _attention(q, k, v):
    """Causal multi-head attention, q and k [T, heads, 192], v [T, heads,
    128] -> [T, heads*128], a block of query rows at a time."""
    T, nh, dq = q.shape
    out = []
    for first in range(0, T, Q_BLOCK):
        rows = slice(first, min(first + Q_BLOCK, T))
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(dq)
        seen = jnp.arange(T)[None, :] <= jnp.arange(T)[rows, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0).reshape(T, -1)


def block(lp, x, model):
    """One block for the tokens x [T, H] at positions 0..T-1."""
    T, _ = x.shape
    nh = model["heads"]
    dn, dr, dv = model["nope_dim"], model["rope_dim"], model["v_dim"]
    rank = model["kv_rank"]
    eps = model.get("rms_eps", 1e-6)
    theta = model.get("rope_theta", 32e6)
    convention = model.get("rope", "interleaved")
    pos = jnp.arange(T)
    y = _rms(x, lp["blk.ln_in.scale"], eps)
    cq = _rms(y @ lp["blk.wq_a"], lp["blk.q_norm.scale"], eps)
    q = (cq @ lp["blk.wq_b"]).reshape(T, nh, dn + dr)
    ckr = y @ lp["blk.wkv_a"]
    c, kr = ckr[:, :rank], ckr[:, rank:]
    if model.get("kv_norm", True):
        c = _rms(c, lp["blk.kv_norm.scale"], eps)
    if model.get("rope_on", "rope") == "rope":
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos, theta, convention)], -1)
    else:       # a test's fault: the wrong 64 lanes of the query turn
        q = jnp.concatenate(
            [_rope(q[..., :dr], pos, theta, convention), q[..., dr:]], -1)
    kr = _rope(kr, pos, theta, convention)
    kv = (c @ lp["blk.wkv_b"]).reshape(T, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kr[:, None, :], (T, nh, dr))], -1)
    h = x + _attention(q, k, kv[..., dn:]) @ lp["blk.wo"]
    y = _rms(h, lp["blk.ln_post.scale"], eps)
    if "blk.mlp_gate" in lp:
        return h + _swiglu(y, lp["blk.mlp_gate"], lp["blk.mlp_up"],
                           lp["blk.mlp_down"])
    return h + _experts(lp, y, model)


def layer_of(params, model, i):
    """Layer i's parameters out of the program's flat set: the leading
    `dense_layers` stacked under `dense.`, the others under `blk.`."""
    lead = model["dense_layers"]
    if i < lead:
        return {"blk." + k[6:]: v[i] for k, v in params.items()
                if k.startswith("dense.")}
    return {k: v[i - lead] for k, v in params.items()
            if k.startswith("blk.")}


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-6))
    return rows @ params["head.w"]


def logits_rows(params, model, ids, first, n_rows):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every layer (stacked); row t
    predicts token t + 1."""
    x = params["wte.w"][ids]
    for i in range(model["layers"]):
        x = block(layer_of(params, model, i), x, model)
    return head_rows(params, model, x, first, n_rows)


MEAN_TIMES = 16     # the mean's weight beside the worst token (`verdict`)


def verdict(gaps) -> float:
    """One number of the sampled tokens' gaps for the tolerance: the WORST
    token's, or `MEAN_TIMES` the MEAN over the tokens where that is larger.
    The worst token shows a fault at one position (a block of the cache, a
    rotation); the mean shows one that moves every token a little (a pair
    of a token's eight dropped, the wrong experts, lower precision), which
    the worst token of a model whose routing bf16 rounding already flips
    here and there shows last. Under bf16 rounding alone most gaps are 0
    and the mean is a fortieth of the worst (configs/joyai_llm_flash.json
    has the chip's readings), so the factor leaves the worst token
    deciding there."""
    gaps = np.asarray(gaps, np.float64)
    return float(max(gaps.max(), MEAN_TIMES * gaps.mean()))


def stream_gaps(top, layer, model, prompts, streams, width):
    """For each (prompt, generated tokens): how far, in float32 logits, each
    generated token lies below the reference's own argmax at its position,
    teacher-forced. Returns (`verdict` of all the gaps, tokens equal to the
    argmax).
    `top` holds the parameters outside the layers, `layer(i)` gives layer
    i's in float32: the sequences go through one layer at a time, and only
    that layer's weights need to exist. Rows are padded to `width` so that
    one program a layer kind serves every stream; causal attention keeps
    the padding out of every row that is read."""
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
            gaps.extend(rows.max(axis=-1) - picked)
            exact += int((rows.argmax(axis=-1) == generated).sum())
    return verdict(gaps), exact
