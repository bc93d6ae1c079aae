"""Plain float32 GPT-2 forward pass (Radford et al. 2019): token + position
embeddings -> pre-LN blocks (causal multi-head attention, GELU MLP) -> final
LayerNorm -> logits through the tied embedding. Straightforward jax.numpy,
one unbatched row at a time: no KV cache, no engine, no kernel, no code of
the program. Parameters are the program's dict with the layers stacked on a
leading axis (`blk.wqkv` is [L, H, 3H]); a Python loop walks them.

Departure shared with the program: GELU is the tanh approximation (GPT-2's
own `gelu_new`)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(lp, x, nh):
    T, H = x.shape
    hd = H // nh
    h = _ln(x, lp["blk.ln1.scale"], lp["blk.ln1.bias"])
    qkv = h @ lp["blk.wqkv"] + lp["blk.bqkv"]
    q, k, v = (t.reshape(T, nh, hd) for t in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + ctx.reshape(T, H) @ lp["blk.wo"] + lp["blk.bo"]
    h = _ln(x, lp["blk.ln2.scale"], lp["blk.ln2.bias"])
    h = _gelu(h @ lp["blk.w1"] + lp["blk.b1"])
    return x + h @ lp["blk.w2"] + lp["blk.b2"]


def logits_rows(params, model, ids, first, n_rows):
    """Logits [n_rows, vocab] of positions first..first+n_rows-1 of the one
    sequence `ids` [T]; row t predicts token t + 1."""
    T = ids.shape[0]
    x = params["wte.w"][ids] + params["wpe.w"][:T]
    blk = {k: v for k, v in params.items() if k.startswith("blk.")}

    # the stacked layers, walked one at a time (fori keeps one block's
    # program instead of 36 unrolled copies; it is still the plain loop)
    def body(i, x):
        return _block({k: v[i] for k, v in blk.items()}, x, model["heads"])

    x = jax.lax.fori_loop(0, model["layers"], body, x)
    x = _ln(x, params["ln_f.scale"], params["ln_f.bias"])
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_rows)
    return rows @ params["wte.w"].T


def stream_gaps(params, model, prompts, streams, width):
    """For each (prompt, generated tokens): how far, in float32 logits, each
    generated token lies below the reference's own argmax at its position,
    teacher-forced. Returns (largest gap, tokens equal to the argmax).
    Rows are padded to `width` so that one program serves every stream;
    causal attention keeps the padding out of every row that is read."""
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n_new = len(streams[0])
    fn = jax.jit(lambda p, ids, first: logits_rows(p, model, ids, first,
                                                   n_new))
    gaps, exact = [], 0
    with jax.default_matmul_precision("highest"):
        for prompt, generated in zip(prompts, streams):
            ids = np.zeros((width,), np.int32)
            ids[:len(prompt) + n_new] = list(prompt) + list(generated)
            rows = np.asarray(fn(p32, jnp.asarray(ids),
                                 np.int32(len(prompt) - 1)), np.float32)
            picked = rows[np.arange(n_new), generated]
            gaps.append(float((rows.max(axis=-1) - picked).max()))
            exact += int((rows.argmax(axis=-1) == generated).sum())
    return max(gaps), exact
