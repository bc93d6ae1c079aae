"""Plain float32 BERT pre-training loss (Devlin et al. 2018): embeddings ->
post-LN encoder blocks -> MLM head on the masked positions + NSP head, in
straightforward jax.numpy. No kernels, no sharding, no dropout (the check
compares the deterministic loss), no code of the program. Parameters are
the program's flat dict (`layer3.attn.q.w`, ...), read by name.

Departures from the published model, shared with the program: GELU is the
tanh approximation, as in the original TensorFlow BERT."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _ln(p, name, x, eps=1e-12):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p[name + ".scale"] \
        + p[name + ".bias"]


def _dense(p, name, x):
    return x @ p[name + ".w"] + p[name + ".b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def encode(p, model, input_ids, token_type_ids):
    B, T = input_ids.shape
    nh = model["heads"]
    hd = model["hidden"] // nh
    x = (p["embeddings.word.w"][input_ids]
         + p["embeddings.position.w"][:T][None]
         + p["embeddings.type.w"][token_type_ids])
    x = _ln(p, "embeddings.ln", x)
    for i in range(model["layers"]):
        a = f"layer{i}.attn"
        q = _dense(p, a + ".q", x).reshape(B, T, nh, hd)
        k = _dense(p, a + ".k", x).reshape(B, T, nh, hd)
        v = _dense(p, a + ".v", x).reshape(B, T, nh, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, nh * hd)
        x = _ln(p, a + ".ln", x + _dense(p, a + ".o", ctx))
        m = f"layer{i}.mlp"
        h = _dense(p, m + ".down", _gelu(_dense(p, m + ".up", x)))
        x = _ln(p, m + ".ln", x + h)
    return x


def encode_f32(params, model, input_ids, token_type_ids):
    """The encoder's output [B, T, H] in float32 at matmul precision
    'highest', as a host array."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, i, t: encode(p, model, i, t))(
                p, jnp.asarray(input_ids), jnp.asarray(token_type_ids)))


def loss_sums(p, model, batch):
    """(sum of MLM negative log-likelihoods, masked count, sum of NSP
    negative log-likelihoods, sequences) of one micro-batch, so that
    micro-batches add up to the loss of the whole batch."""
    seq = encode(p, model, batch["input_ids"], batch["token_type_ids"])
    pos = batch["masked_positions"]
    labels = batch["masked_labels"]
    g = jnp.take_along_axis(seq, pos[..., None], axis=1)
    h = _ln(p, "mlm.ln", _gelu(_dense(p, "mlm.transform", g)))
    logits = h @ p["embeddings.word.w"].T + p["mlm.bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             axis=-1)[..., 0]
    cls = jnp.tanh(_dense(p, "pooler", seq[:, 0]))
    nsp_lp = jax.nn.log_softmax(_dense(p, "nsp", cls), axis=-1)
    nsp = jnp.take_along_axis(nsp_lp, batch["nsp_labels"][:, None], 1)
    return (-(ll * valid).sum(), valid.sum(), -nsp.sum(),
            batch["nsp_labels"].shape[0])


def pretrain_loss(params, model, batch, microbatch: int) -> float:
    """MLM + NSP loss of `batch` in float32 at matmul precision 'highest',
    computed `microbatch` sequences at a time."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n = batch["input_ids"].shape[0]
    fn = jax.jit(lambda p, b: loss_sums(p, model, b))
    mlm = cnt = nsp = seqs = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, microbatch):
            mb = {k: v[lo:lo + microbatch] for k, v in batch.items()}
            a, b, c, d = fn(p, mb)
            mlm += float(a)
            cnt += float(b)
            nsp += float(c)
            seqs += float(d)
    return mlm / max(cnt, 1.0) + nsp / seqs
