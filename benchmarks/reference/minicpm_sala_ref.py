"""Plain float32 MiniCPM-SALA forward pass, written from the model's
`config.json` (openbmb/MiniCPM-SALA, `model_type` `minicpm_sala`) and, for
the sizes it lacks, the MiniCPM4 family's published `sparse_config` and the
InfLLM-V2 and Lightning Attention-2 descriptions (`configs/
minicpm_sala.json` lists them under `assumed`). With `a = scale_depth /
sqrt(depth_layers)`:

    x0 = scale_emb * embed(id)
    h  = h + a * Mixer(RMSNorm(h))                       eps 1e-6, no bias
    h  = h + a * W_d (silu(W_g y) * W_u y),  y = RMSNorm(h)
    logits = head(RMSNorm(h) / (hidden / dim_model_base))

    S   q = y W_q -> heads x D; k, v -> kv_heads x D; RMSNorm a head on q
        and k; NO position enters; query head h reads K/V head h // group.
        The query at position t sees n = t + 1 tokens. n <= dense_len:
        causal softmax over all of them at 1/sqrt(D). Else: compressed keys
        kc_j = mean(k[stride j : stride j + kernel]) for every j with
        stride j + kernel <= n; p_h = softmax_j(q_h . kc_j / sqrt(D));
        P_g = sum of p_h over the heads of K/V head g; a block of
        `sel_block` tokens scores the largest P_g,j among the windows that
        overlap it (0 where none does); the first `init_blocks` blocks and
        the blocks that hold the newest `window` tokens are taken, then the
        best of the rest until `topk` are (ties to the lower index); softmax
        attention over the tokens <= t of the taken blocks.
        out = (ctx * sigmoid(y W_gate)) W_o
    L   q, k, v = y W_q, y W_k, y W_v -> lin_heads x D; RMSNorm a head on q
        and k; rotary (rotate-half, theta, all D lanes) on q and k;
        S_t = exp(-s_h) S_{t-1} + v_t k_t^T, o_t = S_t q_t / sqrt(D),
        s_h = 2^(-8 (h + 1) / heads), S_0 = 0, TOKEN BY TOKEN (a `lax.scan`);
        out = (RMSNorm(o) * sigmoid(y W_gate)) W_o, the norm over all heads

No kernel, no cache, no state pool, no chunked scan, no code of the
program: one unbatched row of tokens at a time, a layer's parameters passed
unstacked under the prefix `blk.`. Attention and the SwiGLU go a block of
positions at a time (`lax.map`) so that 45k tokens fit. Departures from
the published description: none known; what the description leaves open
(the sparse sizes, the slopes, the rotary convention, the extent of the
lightning layers' output norm and gate) is `assumed` in the configuration.

The switches of `model` exist for the tests and the readings behind the
cell's tolerance; their defaults are the model: `dense_walk` (True: the
sparse layers read every token whatever n), `sparse_rope` (True: rotary on
the sparse layers' q and k), `lin_rope` (False: left out of the lightning
layers), `decay_one` (True: the lightning state never decays),
`bf16_state_layer` (i: the i-th lightning layer's state rounded to bfloat16
after every token)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128       # query rows a block of sparse attention
ROW_BLOCK = 2048    # rows a block of the SwiGLU


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_half(x, theta):
    """x [T, heads, d] at positions 0..T-1, rotate-half over all of d."""
    T, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks_of_rows(fn, rows: int, T: int):
    """`fn(first)` for every block of `rows` rows of 0..T-1 (T whole
    blocks), results concatenated on the first axis."""
    out = jax.lax.map(fn, jnp.arange(0, T, rows, dtype=jnp.int32))
    return out.reshape((T,) + out.shape[2:])


def taken_blocks(P, n, model, nsb: int):
    """Which blocks a query reads: P `[G, NJ]` the group sums of the
    compressed-key softmax (0 at windows that are not complete), n the
    tokens the query sees -> bool `[G, nsb]`. Every window that can
    overlap a block is looked at with the overlap written out, and the
    pick is one stable sort."""
    stride, kernel = model["kernel_stride"], model["kernel_size"]
    sb, topk = model["sel_block"], model["topk"]
    NJ = P.shape[-1]
    b = jnp.arange(nsb)
    # the windows from the first that can reach block b on
    j = ((sb * b - kernel) // stride + 1)[:, None] \
        + jnp.arange((sb + kernel) // stride + 1)[None, :]  # [nsb, few]
    overlap = (j >= 0) & (j < NJ) & (stride * j < sb * (b[:, None] + 1)) \
        & (stride * j + kernel > sb * b[:, None])
    score = jnp.max(jnp.where(overlap[None], P[:, jnp.clip(j, 0, NJ - 1)],
                              0.0), axis=-1)                # [G, nsb]
    exists = b <= (n - 1) // sb
    forced = (b < model["init_blocks"]) \
        | (b >= jnp.maximum(n - model["window"], 0) // sb)
    key = jnp.where(exists & forced, jnp.inf,
                    jnp.where(exists, score, -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)         # best first
    rank = jnp.argsort(order, axis=-1)
    return (rank < topk) & exists


def _sparse(lp, y, model):
    """The block-sparse attention layer for the tokens y [T, H]."""
    T = y.shape[0]
    nh, kvh, d = model["heads"], model["kv_heads"], model["head_dim"]
    stride, kernel = model["kernel_stride"], model["kernel_size"]
    sb = model["sel_block"]
    eps = model.get("rms_eps", 1e-6)
    group = nh // kvh
    q = _rms((y @ lp["blk.wq"]).reshape(T, nh, d), lp["blk.q_norm"], eps)
    k = _rms((y @ lp["blk.wk"]).reshape(T, kvh, d), lp["blk.k_norm"], eps)
    v = (y @ lp["blk.wv"]).reshape(T, kvh, d)
    if model.get("sparse_rope", False):
        q, k = _rope_half(q, model["rope_theta"]), \
            _rope_half(k, model["rope_theta"])
    NJ = max((T - kernel) // stride + 1, 1)
    at = stride * jnp.arange(NJ)[:, None] + jnp.arange(kernel)[None, :]
    kc = jnp.mean(k[jnp.minimum(at, T - 1)], axis=1)        # [NJ, kvh, d]
    nsb = -(-T // sb)
    tok = jnp.arange(T)
    scale = 1.0 / math.sqrt(d)
    dense_all = bool(model.get("dense_walk", False))

    def rows_from(first):
        qs = jax.lax.dynamic_slice_in_dim(q, first, Q_BLOCK)
        qs = qs.reshape(Q_BLOCK, kvh, group, d)
        t = first + jnp.arange(Q_BLOCK)
        n = t + 1
        complete = stride * jnp.arange(NJ)[None, :] + kernel <= n[:, None]
        s = jnp.einsum("qgrd,jgd->qgrj", qs, kc) * scale
        s = jnp.where(complete[:, None, None, :], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(complete[:, None, None, :],
                      jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                      0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        P = jnp.sum(p, axis=2)                              # [Q, kvh, NJ]
        taken = jax.vmap(lambda Pq, nq: taken_blocks(Pq, nq, model, nsb))(
            P, n)                                           # [Q, kvh, nsb]
        seen = jnp.repeat(taken, sb, axis=-1)[..., :T]
        if dense_all:
            seen = jnp.ones_like(seen)
        else:
            seen = seen | (n <= model["dense_len"])[:, None, None]
        seen = seen & (tok[None, None, :] <= t[:, None, None])
        a = jnp.einsum("qgrd,kgd->qgrk", qs, k) * scale
        a = jax.nn.softmax(jnp.where(seen[:, :, None, :], a, -jnp.inf),
                           axis=-1)
        return jnp.einsum("qgrk,kgd->qgrd", a, v).reshape(Q_BLOCK, nh * d)

    ctx = _blocks_of_rows(rows_from, Q_BLOCK, T)
    return (ctx * jax.nn.sigmoid(y @ lp["blk.wg"])) @ lp["blk.wo"]


def slopes(model):
    h = jnp.arange(1, model["lin_heads"] + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / model["lin_heads"])


def _lightning(lp, y, model, nth: int):
    """The lightning (linear) attention layer for the tokens y [T, H],
    token by token; `nth` is the layer's index among its kind."""
    T = y.shape[0]
    nh, d = model["lin_heads"], model["lin_head_dim"]
    eps = model.get("rms_eps", 1e-6)
    q = _rms((y @ lp["blk.wq"]).reshape(T, nh, d), lp["blk.q_norm"], eps)
    k = _rms((y @ lp["blk.wk"]).reshape(T, nh, d), lp["blk.k_norm"], eps)
    v = (y @ lp["blk.wv"]).reshape(T, nh, d)
    if model.get("lin_rope", True):
        q, k = _rope_half(q, model["rope_theta"]), \
            _rope_half(k, model["rope_theta"])
    decay = jnp.ones((nh,), jnp.float32) if model.get("decay_one") \
        else jnp.exp(-slopes(model))
    sdt = jnp.bfloat16 if model.get("bf16_state_layer") == nth \
        else jnp.float32

    def token(S, t):
        qt, kt, vt = t
        S = decay[:, None, None] * S.astype(jnp.float32) \
            + vt[:, :, None] * kt[:, None, :]
        S = S.astype(sdt)
        o = jnp.sum(S.astype(jnp.float32) * qt[:, None, :], axis=-1)
        return S, o / math.sqrt(d)

    _, o = jax.lax.scan(token, jnp.zeros((nh, d, d), sdt), (q, k, v))
    o = _rms(o.reshape(T, nh * d), lp["blk.onorm.scale"], eps)
    return (o * jax.nn.sigmoid(y @ lp["blk.wg"])) @ lp["blk.wo"]


def _swiglu(lp, y):
    T = y.shape[0]
    rows = min(ROW_BLOCK, T)

    def rows_from(first):
        r = jax.lax.dynamic_slice_in_dim(y, first, rows)
        return (jax.nn.silu(r @ lp["blk.w_gate"]) * (r @ lp["blk.w_up"])) \
            @ lp["blk.w_down"]

    return _blocks_of_rows(rows_from, rows, T)


def block(lp, x, model, kind, nth: int = 0):
    """One block of `kind` (`*` sparse attention, `M` lightning attention,
    `E` the SwiGLU) for the tokens x [T, H] at positions 0..T-1, T whole
    blocks of `Q_BLOCK` and of min(`ROW_BLOCK`, T) rows."""
    a = model["scale_depth"] / math.sqrt(model["depth_layers"])
    y = _rms(x, lp["blk.norm.scale"], model.get("rms_eps", 1e-6))
    if kind == "*":
        return x + a * _sparse(lp, y, model)
    if kind == "M":
        return x + a * _lightning(lp, y, model, nth)
    if kind == "E":
        return x + a * _swiglu(lp, y)
    raise ValueError(f"unknown block kind {kind!r}")


def pattern(model) -> str:
    return "".join("*E" if m == "S" else "ME" for m in model["mixers"])


PREFIX = {"*": "attn.", "M": "lin.", "E": "mlp."}


def layer_of(params, model, i):
    """Block i's parameters out of the program's flat set (the blocks of a
    kind stacked under the kind's prefix, in the pattern's order)."""
    pat = pattern(model)
    kind = pat[i]
    nth = pat[:i].count(kind)
    prefix = PREFIX[kind]
    return {"blk." + k[len(prefix):]: v[nth] for k, v in params.items()
            if k.startswith(prefix)}


def embed(top, model, ids):
    return top["wte.w"][ids] * model["scale_emb"]


def head_rows(params, model, x, first, n_rows):
    """Logits [n_rows, vocab] of rows first..first+n_rows-1 of x [T, H]."""
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    rows = _rms(rows, params["ln_f.scale"], model.get("rms_eps", 1e-6)) \
        / (model["hidden"] / model["dim_model_base"])
    return rows @ params["head.w"]


def logits_rows(params, model, ids, first, n_rows):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T], `params` holding every block (stacked); row t
    predicts token t + 1."""
    x = embed(params, model, ids)
    pat = pattern(model)
    for i, kind in enumerate(pat):
        x = block(layer_of(params, model, i), x, model, kind,
                  pat[:i].count(kind))
    return head_rows(params, model, x, first, n_rows)


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
    outside the blocks, `layer(i)` gives block i's in float32: the
    sequences go through one block at a time, and only that block's
    weights need to exist. Rows are padded to the longest sequence's
    length rounded up to whole blocks of rows, so that one program a block
    kind serves every sequence; a causal model keeps the padding out of
    every row that is read. `weights(name, value)` is a control on the
    parameters (rounding them to a lower precision), applied a tensor at a
    time."""
    weights = weights or (lambda k, v: v)
    top = {k: weights(k, jnp.asarray(v, jnp.float32))
           for k, v in top.items()}
    longest = max(len(s) for s in sequences)
    unit = ROW_BLOCK if longest > ROW_BLOCK else Q_BLOCK
    width = -(-longest // unit) * unit
    pat = pattern(model)
    steps = {}

    def step_of(kind, nth):
        # a lightning layer's index matters to `bf16_state_layer` alone
        key = (kind, nth if kind == "M" and model.get("bf16_state_layer")
               is not None else 0)
        if key not in steps:
            steps[key] = jax.jit(lambda lp, x: block(lp, x, model, *key))
        return steps[key]

    head = jax.jit(lambda p, x, first: head_rows(p, model, x, first, n_rows))
    with jax.default_matmul_precision("highest"):
        xs = []
        for seq in sequences:
            ids = np.zeros((width,), np.int32)
            ids[:len(seq)] = list(seq)
            xs.append(embed(top, model, jnp.asarray(ids)))
        for i, kind in enumerate(pat):
            raw = dict(layer(i))
            lp = {k: weights(k, jnp.asarray(raw.pop(k), jnp.float32))
                  for k in list(raw)}
            step = step_of(kind, pat[:i].count(kind))
            xs = [step(lp, x) for x in xs]
            del lp
        return [np.asarray(head(top, x, np.int32(len(seq) - n_rows - 1)),
                           np.float32) for x, seq in zip(xs, sequences)]


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
