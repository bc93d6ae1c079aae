"""BERT (encoder-only transformer) — the flagship benchmark model.

Reference capability: BERT-base served/trained by the reference via its
op zoo (matmul/softmax/layer_norm + Adam; inference/tests/api/
analyzer_bert_tester.cc exercises the graph). Rebuilt TPU-first:

- bf16 activations, fp32 params/LN stats → MXU-friendly
- attention through ops/pallas/attention.mha, whose gate picks the route
  from the shape: on a TPU an unmasked sequence of 128, 256 or 512 tokens
  (the pretraining cells' T = 128) goes through the fused short-sequence
  kernel, forward and one-pass backward, with no [B, heads, T, T] buffer in
  HBM; T >= 1024 through splash; a padding mask, any other length and
  every run off the chip through the XLA einsum + softmax path
- logical sharding axes: batch→dp, seq→sp, heads/mlp/vocab→tp — megatron TP
  + sequence parallelism come from the rule table, no model change
  (parallel/sharding.py)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from .common import (ParamStore, Params, dense, dropout, gelu, layer_norm)


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    type_vocab: int = 2
    dropout: float = 0.1
    dtype: str = "bfloat16"  # activation dtype

    @staticmethod
    def base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def tiny() -> "BertConfig":
        return BertConfig(vocab_size=1024, hidden=64, layers=2, heads=4,
                          mlp_dim=128, max_len=64, dropout=0.0)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    def flops_per_token(self) -> float:
        """Training FLOPs/token with dense-MLM (legacy MFU accounting)."""
        return self.train_flops_per_seq(self.max_len, self.max_len) / self.max_len

    def train_flops_per_seq(self, seq_len: int, n_masked: int) -> float:
        """Training FLOPs per sequence: 3x forward; forward = 2*T*matmul
        params + attention quadratic term + masked-only vocab projection."""
        H, M, L = self.hidden, self.mlp_dim, self.layers
        matmul_params = L * (4 * H * H + 2 * H * M) + 2 * H * H  # + mlm/pooler
        fwd = (2 * seq_len * matmul_params
               + L * 4 * seq_len * seq_len * H
               + 2 * n_masked * self.vocab_size * H)
        return 3 * fwd


def init(rng: jax.Array, cfg: BertConfig) -> Tuple[Params, Dict]:
    s = ParamStore(rng, jnp.float32)
    s.embedding("embeddings.word", cfg.vocab_size, cfg.hidden,
                axes=("vocab", "embed"))
    s.embedding("embeddings.position", cfg.max_len, cfg.hidden,
                axes=(None, "embed"))
    s.embedding("embeddings.type", cfg.type_vocab, cfg.hidden,
                axes=(None, "embed"))
    s.layer_norm("embeddings.ln", cfg.hidden)
    for i in range(cfg.layers):
        p = f"layer{i}"
        s.dense(f"{p}.attn.q", cfg.hidden, cfg.hidden, axes=("embed", "heads"))
        s.dense(f"{p}.attn.k", cfg.hidden, cfg.hidden, axes=("embed", "heads"))
        s.dense(f"{p}.attn.v", cfg.hidden, cfg.hidden, axes=("embed", "heads"))
        s.dense(f"{p}.attn.o", cfg.hidden, cfg.hidden, axes=("heads", "embed"))
        s.layer_norm(f"{p}.attn.ln", cfg.hidden)
        s.dense(f"{p}.mlp.up", cfg.hidden, cfg.mlp_dim, axes=("embed", "mlp"))
        s.dense(f"{p}.mlp.down", cfg.mlp_dim, cfg.hidden, axes=("mlp", "embed"))
        s.layer_norm(f"{p}.mlp.ln", cfg.hidden)
    s.dense("pooler", cfg.hidden, cfg.hidden, axes=("embed", "embed"))
    # MLM head: transform + tied-embedding output bias
    s.dense("mlm.transform", cfg.hidden, cfg.hidden, axes=("embed", "embed"))
    s.layer_norm("mlm.ln", cfg.hidden)
    s.add("mlm.bias", jnp.zeros((cfg.vocab_size,), jnp.float32), ("vocab",))
    s.dense("nsp", cfg.hidden, 2, axes=("embed", None))
    return s.params, s.axes


def _attention(params: Params, prefix: str, x: jax.Array, mask: jax.Array,
               cfg: BertConfig, rng, deterministic: bool) -> jax.Array:
    B, T, H = x.shape
    nh, hd = cfg.heads, cfg.head_dim
    # layer scopes as in models/gpt.py: HLO metadata a profile reduces by
    with jax.named_scope("qkv"):
        q = dense(params, f"{prefix}.q", x).reshape(B, T, nh, hd)
        k = dense(params, f"{prefix}.k", x).reshape(B, T, nh, hd)
        v = dense(params, f"{prefix}.v", x).reshape(B, T, nh, hd)
        q = shard(q, ("batch", "seq", "heads", None))
        k = shard(k, ("batch", "seq", "heads", None))
        v = shard(v, ("batch", "seq", "heads", None))

    from ..ops.pallas import attention as pallas_attention

    with jax.named_scope("attention"):
        ctx = pallas_attention.mha(q, k, v, mask=mask,
                                   scale=1.0 / math.sqrt(hd))
    with jax.named_scope("proj"):
        out = dense(params, f"{prefix}.o", ctx.reshape(B, T, H))
        return dropout(rng, out, cfg.dropout, deterministic)


def encode(params: Params, cfg: BertConfig, input_ids: jax.Array,
           token_type_ids: Optional[jax.Array] = None,
           attention_mask: Optional[jax.Array] = None,
           rng: Optional[jax.Array] = None,
           deterministic: bool = True) -> jax.Array:
    """Returns [B, T, H] sequence output (activations in cfg.dtype)."""
    B, T = input_ids.shape
    adt = jnp.dtype(cfg.dtype)
    if token_type_ids is None:
        token_type_ids = jnp.zeros_like(input_ids)

    with jax.named_scope("embed"):
        emb = (params["embeddings.word.w"][input_ids]
               + params["embeddings.position.w"][:T][None, :, :]
               + params["embeddings.type.w"][token_type_ids])
        x = layer_norm(params, "embeddings.ln", emb).astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    rngs = (jax.random.split(rng, cfg.layers * 2)
            if rng is not None else [None] * (cfg.layers * 2))
    # additive mask [B, 1, 1, T]; None = padding-free (no mask buffer at all,
    # which keeps the flash-attention path O(T) in memory)
    if attention_mask is None:
        amask = None
    else:
        neg = jnp.asarray(-1e9 if adt == jnp.float32 else -3e4, jnp.float32)
        amask = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, neg)

    with jax.named_scope("layers"):
        for i in range(cfg.layers):
            p = f"layer{i}"
            a = _attention(params, f"{p}.attn", x, amask, cfg, rngs[2 * i],
                           deterministic)
            x = layer_norm(params, f"{p}.attn.ln", x + a)
            x = shard(x, ("batch", "seq", "embed"))
            with jax.named_scope("mlp"):
                h = dense(params, f"{p}.mlp.up", x, act=gelu)
                h = shard(h, ("batch", "seq", "mlp"))
                h = dense(params, f"{p}.mlp.down", h)
                h = dropout(rngs[2 * i + 1], h, cfg.dropout, deterministic)
            x = layer_norm(params, f"{p}.mlp.ln", x + h)
            x = shard(x, ("batch", "seq", "embed"))
    return x


@jax.named_scope("mlm_head")
def mlm_logits(params: Params, cfg: BertConfig, seq_out: jax.Array) -> jax.Array:
    h = dense(params, "mlm.transform", seq_out, act=gelu)
    h = layer_norm(params, "mlm.ln", h)
    logits = h @ params["embeddings.word.w"].T.astype(h.dtype)
    logits = logits + params["mlm.bias"].astype(h.dtype)
    return shard(logits, ("batch", "seq", "vocab"))


def pretrain_loss(params: Params, cfg: BertConfig, batch: Dict[str, jax.Array],
                  rng: Optional[jax.Array] = None,
                  deterministic: bool = False) -> jax.Array:
    """Masked-LM + next-sentence loss (the BERT-base pretrain objective).

    Two MLM batch formats:
    - gathered (preferred, what BERT's max_predictions_per_seq does):
      "masked_positions" [B, P] + "masked_labels" [B, P] (-100 = pad slot) —
      only P positions hit the vocab projection.
    - dense: "mlm_labels" [B, T] with -100 for unmasked positions.
    """
    seq = encode(params, cfg, batch["input_ids"],
                 batch.get("token_type_ids"), batch.get("attention_mask"),
                 rng=rng, deterministic=deterministic)
    if "masked_positions" in batch:
        pos = batch["masked_positions"]  # [B, P]
        labels = batch["masked_labels"]
        gathered = jnp.take_along_axis(
            seq, pos[..., None].astype(jnp.int32), axis=1)  # [B, P, H]
        logits = mlm_logits(params, cfg, gathered).astype(jnp.float32)
    else:
        labels = batch["mlm_labels"]  # [B, T], -100 = unmasked
        logits = mlm_logits(params, cfg, seq).astype(jnp.float32)
    with jax.named_scope("loss"):
        valid = labels >= 0
        lab = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tok_ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        mlm = -(tok_ll * valid).sum() / jnp.maximum(valid.sum(), 1)

    if "nsp_labels" in batch:
        with jax.named_scope("nsp_head"):
            cls = jnp.tanh(
                dense(params, "pooler", seq[:, 0]).astype(jnp.float32))
            nsp_logits = dense(
                params, "nsp", cls.astype(seq.dtype)).astype(jnp.float32)
        with jax.named_scope("loss"):
            nsp_lp = jax.nn.log_softmax(nsp_logits, axis=-1)
            nsp = -jnp.take_along_axis(
                nsp_lp, batch["nsp_labels"][:, None], 1).mean()
        return mlm + nsp
    return mlm


def make_batch(rng: jax.Array, cfg: BertConfig, batch_size: int,
               seq_len: Optional[int] = None,
               max_predictions: Optional[int] = None) -> Dict[str, jax.Array]:
    """Synthetic pretraining batch in the gathered format (benchmark input).
    max_predictions defaults to ceil(0.15 * T) like BERT's
    max_predictions_per_seq."""
    T = seq_len or cfg.max_len
    P = max_predictions or max(1, int(0.15 * T) + 1)
    k1, k2, k3 = jax.random.split(rng, 3)
    ids = jax.random.randint(k1, (batch_size, T), 0, cfg.vocab_size)
    # first P positions of a random permutation are masked
    perm = jax.vmap(lambda k: jax.random.permutation(k, T))(
        jax.random.split(k2, batch_size))
    pos = jnp.sort(perm[:, :P], axis=-1)
    labels = jnp.take_along_axis(ids, pos, axis=1)
    masked_ids = jax.vmap(lambda row, p: row.at[p].set(103))(ids, pos)
    # no attention_mask: benchmark batches are padding-free, and its absence
    # selects the maskless flash-attention path
    return {
        "input_ids": masked_ids,
        "token_type_ids": jnp.zeros((batch_size, T), jnp.int32),
        "masked_positions": pos,
        "masked_labels": labels,
        "nsp_labels": jax.random.randint(k3, (batch_size,), 0, 2),
    }
