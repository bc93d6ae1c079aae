"""Plain float32 Xing4.0-29B-A4B forward pass, written from the model's
`config.json` (XingChen-AGI/Xing4.0-29B-A4B, `model_type` xing4_0) and the
two papers its extra keys point to: the block is DeepSeek-V3's (latent
attention, `first_k_dense_replace` dense SwiGLUs, then sigmoid-routed
experts with a shared one) with YaRN on the rotary lanes and a residual path
of `hc_mult` streams mixed by manifold-constrained hyper-connections
(arXiv:2512.24880; the streams' copies in and sum out are the
hyper-connections paper's, arXiv:2409.19606).

A token carries X [n, C] (n = `hc_mult` 4, C = `hidden` 3584); X_0 is the
embedding copied into all n streams. Each block has two sub-layers F
(attention, then the dense MLP or the expert layer), each with its own
`phi`, `a` = (a_pre, a_post, a_res), `b_pre`, `b_post`, `b_res`:

    x^      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)            no gain
    [p|q|R] = x^ Phi                      Phi [nC, 2n + n^2]: 4 | 4 | 16
    H_pre   = sigmoid(a_pre p + b_pre);  H_post = 2 sigmoid(a_post q + b_post)
    M       = exp(clip(a_res mat(R) + b_res, -30, 30))
    `hc_sinkhorn_iters` (20) times:  M <- M / (column sums + hc_eps),
                                     M <- M / (row sums + hc_eps);  H_res = M
    u       = H_pre X;   y = F(RMSNorm(u));   X' = H_res X + H_post^T y

and after the last block the n streams are summed, then the final RMSNorm
and an output head of its own. F is `joyai_ref.py`'s block half for half
(eps 1e-6, no biases; that file's header has the equations), with hidden
3584, `q_lora_rank` 768, 32 heads of 128 + 64 rotary and values of 128, and
two differences, both YaRN's (DeepSeek-V3's convention, `rope_scaling`:
factor s 64, original length L0 4096, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 1): rotary pair i of 32 turns by position x inv_freq_i,

    f_i = theta^(-2i/64);   d(r) = 64 ln(L0 / (r 2 pi)) / (2 ln theta)
    low = floor(d(32)) = 10,  high = ceil(d(1)) = 23
    g_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = (1 - g_i) f_i + g_i f_i / s

whatever the sequence's length, and with m(a) = 0.1 a ln(s) + 1 the scores
are multiplied by m(mscale_all_dim)^2 / sqrt(192) (cos and sin by m(mscale)
/ m(mscale_all_dim) = 1). The rotation is INTERLEAVED (pairs (2i, 2i+1)).
The second half: a dense layer is a SwiGLU of 9216; an expert layer takes
the top-4 of 64 by sigmoid score + correction bias, weighs them by score /
(sum of the four + 1e-20) x 2.0, and adds one shared expert unweighted.

The program stores Phi TRANSPOSED (`phi` [2n + n^2, nC]; a matter of the
device's tiles) and carries vec(X); this file reads `phi.T` and carries X
[T, n, C]. Straightforward jax.numpy, one unbatched sequence at a time: no
cache, no engine, no kernel, no sorting or grouping of tokens, no code of
the program. Every expert is computed for every token and masked by the
router's choice; attention is the EXPANDED form, in blocks of query rows,
and the wide products go in blocks of rows, so that a 17k-token sequence
fits beside one float32 expert layer. A layer's parameters are passed
unstacked under the prefix `blk.`.

The switches of `model` exist for the controls that show what the
comparison tells apart (`tests/benchmarks/xing4_control.py`); their defaults
are the published model: `h_res_identity` (True: H_res = I), `post_gain`
(1.0: H_post without its factor 2), `drop_stream` (i: stream i left out of
the final sum), `yarn` (False: the plain frequencies f_i), `mscale2`
(False: the scores by 1/sqrt(192) alone), `sinkhorn_iters` (another count),
`shared_expert` (False), `route_scale`."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # query rows a block of attention
ROW_BLOCK = 2048    # rows a block of the wide products


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _blocks_of_rows(fn, rows, T):
    """`fn(first)` for whole blocks of `rows` rows of T, stacked back."""
    out = jax.lax.map(fn, jnp.arange(0, T, rows, dtype=jnp.int32))
    return out.reshape((T,) + out.shape[2:])


def yarn(model):
    """(inv_freq [rope_dim / 2], what the scores are multiplied by)."""
    d, theta = model["rope_dim"], float(model.get("rope_theta", 10000.0))
    s, L0 = float(model["rope_factor"]), float(model["rope_orig_len"])
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)

    def pair(turns):
        return d * math.log(L0 / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(pair(model.get("rope_beta_fast", 32.0))), 0)
    high = min(math.ceil(pair(model.get("rope_beta_slow", 1.0))), d - 1)
    g = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    inv = (1.0 - g) * f + g * f / s if model.get("yarn", True) else f
    m = 0.1 * float(model.get("rope_mscale_all_dim", 1.0)) * math.log(s) + 1.0
    scale = (m * m if model.get("mscale2", True) else 1.0) \
        / math.sqrt(model["nope_dim"] + d)
    return jnp.asarray(inv, jnp.float32), scale


def _rope(x, pos, inv):
    """x [T, ..., d], pos [T]: interleaved pair i turns by pos * inv[i]."""
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(y, gate, up, down):
    g = y @ gate
    return (g * jax.nn.sigmoid(g) * (y @ up)) @ down


def _experts(lp, y, model):
    """sum_e w_e expert_e(y) over each token's chosen experts + the shared
    expert; y [T, H]."""
    k = model["top_k"]
    s = jax.nn.sigmoid(y @ lp["blk.router"])
    biased = s + lp["blk.router_bias"]
    keep = biased >= jnp.sort(biased, axis=-1)[:, -k][:, None]
    w = jnp.where(keep, s, 0.0)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * model.get("route_scale", 2.0)

    def one(e, acc):
        return acc + w[:, e][:, None] * _swiglu(
            y, lp["blk.w_gate"][e], lp["blk.w_up"][e], lp["blk.w_down"][e])

    out = jax.lax.fori_loop(0, s.shape[-1], one, jnp.zeros_like(y))
    if model.get("shared_expert", True):
        out = out + _swiglu(y, lp["blk.shared_gate"], lp["blk.shared_up"],
                            lp["blk.shared_down"])
    return out


def _attention(q, k, v, scale):
    """Causal multi-head attention, q and k [T, heads, 192], v [T, heads,
    128] -> [T, heads*128], `Q_BLOCK` query rows at a time."""
    T = q.shape[0]
    rows = min(Q_BLOCK, T)

    def rows_from(first):
        qs = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        seen = jnp.arange(T)[None, :] <= (first + jnp.arange(rows))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return _blocks_of_rows(rows_from, rows, T).reshape(T, -1)


def attention(lp, y, model):
    """The attention sub-layer's F for the tokens y [T, H] at 0..T-1,
    output projection included."""
    T = y.shape[0]
    nh = model["heads"]
    dn, dr, dv = model["nope_dim"], model["rope_dim"], model["v_dim"]
    rank = model["kv_rank"]
    eps = model.get("rms_eps", 1e-6)
    inv, scale = yarn(model)
    pos = jnp.arange(T)
    cq = _rms(y @ lp["blk.wq_a"], lp["blk.q_norm.scale"], eps)
    q = (cq @ lp["blk.wq_b"]).reshape(T, nh, dn + dr)
    ckr = y @ lp["blk.wkv_a"]
    c = _rms(ckr[:, :rank], lp["blk.kv_norm.scale"], eps)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv)], -1)
    kr = _rope(ckr[:, rank:], pos, inv)
    kv = (c @ lp["blk.wkv_b"]).reshape(T, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kr[:, None, :], (T, nh, dr))], -1)
    return _attention(q, k, kv[..., dn:], scale) @ lp["blk.wo"]


def second_half(lp, y, model):
    """The second sub-layer's F: the dense SwiGLU (a layer whose parameters
    hold `blk.mlp_gate`) or the expert layer, `ROW_BLOCK` rows at a time."""
    T = y.shape[0]
    rows = min(ROW_BLOCK, T)

    def rows_from(first):
        r = jax.lax.dynamic_slice_in_dim(y, first, rows)
        if "blk.mlp_gate" in lp:
            return _swiglu(r, lp["blk.mlp_gate"], lp["blk.mlp_up"],
                           lp["blk.mlp_down"])
        return _experts(lp, r, model)

    return _blocks_of_rows(rows_from, rows, T)


def sinkhorn(M, iters, eps):
    """M [T, n, n] (entry (i, j) at M[t, i, j]): `iters` rounds of columns
    then rows."""
    for _ in range(iters):
        M = M / (M.sum(axis=1, keepdims=True) + eps)
        M = M / (M.sum(axis=2, keepdims=True) + eps)
    return M


def maps(lp, X, model, which):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of sub-layer `which`
    ("attn" | "mlp") for X [T, n, C]."""
    T, n, _ = X.shape
    eps = model.get("hc_eps", 1e-6)
    p = f"blk.hc_{which}."
    v = X.reshape(T, -1)
    xhat = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    z = xhat @ lp[p + "phi"].T
    a_pre, a_post, a_res = lp[p + "a"]
    H_pre = jax.nn.sigmoid(a_pre * z[:, :n] + lp[p + "b_pre"])
    H_post = model.get("post_gain", 2.0) * jax.nn.sigmoid(
        a_post * z[:, n:2 * n] + lp[p + "b_post"])
    A = jnp.clip(a_res * z[:, 2 * n:].reshape(T, n, n) + lp[p + "b_res"],
                 model.get("hc_clamp_min", -30.0),
                 model.get("hc_clamp_max", 30.0))
    H_res = sinkhorn(jnp.exp(A), model.get("sinkhorn_iters",
                                           model["hc_sinkhorn_iters"]), eps)
    if model.get("h_res_identity", False):
        H_res = jnp.broadcast_to(jnp.eye(n, dtype=X.dtype), (T, n, n))
    return H_pre, H_post, H_res


def sub_layer(lp, X, model, which, F):
    H_pre, H_post, H_res = maps(lp, X, model, which)
    u = jnp.einsum("tn,tnc->tc", H_pre, X)
    norm = "blk.ln_in.scale" if which == "attn" else "blk.ln_post.scale"
    y = F(lp, _rms(u, lp[norm], model.get("rms_eps", 1e-6)), model)
    return jnp.einsum("tij,tjc->tic", H_res, X) \
        + H_post[:, :, None] * y[:, None, :]


def block(lp, X, model):
    """One block for the tokens X [T, n, C] at positions 0..T-1."""
    X = sub_layer(lp, X, model, "attn", attention)
    return sub_layer(lp, X, model, "mlp", second_half)


def layer_of(params, model, i):
    """Layer i's parameters out of the program's flat set: the leading
    `dense_layers` stacked under `dense.`, the others under `blk.`."""
    lead = model["dense_layers"]
    if i < lead:
        return {"blk." + k[6:]: v[i] for k, v in params.items()
                if k.startswith("dense.")}
    return {k: v[i - lead] for k, v in params.items()
            if k.startswith("blk.")}


def embed(top, model, ids):
    x = top["wte.w"][ids]
    return jnp.broadcast_to(x[:, None, :],
                            (x.shape[0], model["hc_mult"], x.shape[1]))


def head_rows(params, model, X, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of X [T, n,
    C]: the streams summed, the final norm, the head."""
    rows = jax.lax.dynamic_slice_in_dim(X, first, n_rows)
    drop = model.get("drop_stream")
    if drop is not None:
        rows = rows.at[:, drop].set(0.0)
    rows = _rms(rows.sum(axis=1), params["ln_f.scale"],
                model.get("rms_eps", 1e-6))
    return rows @ params["head.w"]


def logits_rows(params, model, ids, first, n_rows):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every layer (stacked); row t
    predicts token t + 1."""
    X = embed(params, model, ids)
    for i in range(model["layers"]):
        X = block(layer_of(params, model, i), X, model)
    return head_rows(params, model, X, first, n_rows)


MEAN_TIMES = 16     # the mean's weight beside the worst token (`verdict`)


def verdict(gaps) -> float:
    """One number of the sampled tokens' gaps for the tolerance: the WORST
    token's, or `MEAN_TIMES` the MEAN over the tokens where that is larger
    (`joyai_ref.verdict` says why both)."""
    gaps = np.asarray(gaps, np.float64)
    return float(max(gaps.max(), MEAN_TIMES * gaps.mean()))


def stream_rows(top, layer, model, sequences, n_rows, weights=None):
    """The float32 logits `[n_rows, vocab]` that predict the LAST `n_rows`
    tokens of each sequence, teacher-forced. `top` holds the parameters
    outside the layers, `layer(i)` gives layer i's in float32. ONE sequence
    is on the device at a time and goes through the layers in turn, each
    made when it is asked for (a sequence's four float32 streams are 1 GB
    at 17k tokens, a float32 expert layer 3 GB, embedding and head 3.8 GB).
    Rows are padded to the longest sequence's length in whole blocks of
    rows, so that one program a layer kind serves every sequence; a causal
    model keeps the padding out of every row that is read. `weights(name,
    value)` is a control on the parameters (rounding them to a lower
    precision), applied a tensor at a time."""
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, jnp.asarray(v, jnp.float32))
           for k, v in top.items()}
    longest = max(len(s) for s in sequences)
    unit = ROW_BLOCK if longest > ROW_BLOCK else Q_BLOCK
    width = -(-longest // unit) * unit
    step = jax.jit(lambda lp, X: block(lp, X, model), donate_argnums=1)
    head = jax.jit(lambda p, X, first: head_rows(p, model, X, first, n_rows))
    rows = []
    with jax.default_matmul_precision("highest"):
        for seq in sequences:
            ids = np.zeros((width,), np.int32)
            ids[:len(seq)] = list(seq)
            X = embed(top, model, jnp.asarray(ids))
            for i in range(model["layers"]):
                lp = {k: weights(k, jnp.asarray(v, jnp.float32))
                      for k, v in layer(i).items()}
                X = step(lp, X)
                del lp
            rows.append(np.asarray(
                head(top, X, np.int32(len(seq) - n_rows - 1)), np.float32))
            del X
    return rows


def gaps_of(rows, picks):
    """How far each pick lies below its row's best, all sequences'."""
    gaps = []
    for r, p in zip(rows, picks):
        gaps.extend(r.max(axis=-1) - r[np.arange(len(p)), np.asarray(p)])
    return gaps


def stream_gaps(top, layer, model, prompts, streams, width):
    """For each (prompt, generated tokens): how far, in float32 logits, each
    generated token lies below the reference's own argmax at its position,
    teacher-forced (`stream_rows`). Returns (`verdict` of all the gaps,
    tokens equal to the argmax). `width` bounds nothing here: a sequence is
    as long as it is."""
    n_new = len(streams[0])
    rows = stream_rows(top, layer, model,
                       [list(p) + list(g) for p, g in zip(prompts, streams)],
                       n_new)
    exact = sum(int((r.argmax(axis=-1) == np.asarray(g)).sum())
                for r, g in zip(rows, streams))
    return verdict(gaps_of(rows, streams)), exact
