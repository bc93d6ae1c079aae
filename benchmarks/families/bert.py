"""BERT as the benchmark drives it: `paddle_tpu.models.bert` through the
program's own entry points, plus the pieces the benchmark keeps for itself
(host batch generator, FLOP count, plain reference)."""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from ..harness import device, shapes
from ..reference import bert_ref

MASK_ID = 103


def make_config(model: Dict):
    from paddle_tpu.models import bert

    return bert.BertConfig(**model)


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import bert

    return device.init_on_device(bert.init, cfg, seed, dtype)


def loss_fn(cfg):
    from paddle_tpu.models import bert

    def loss(params, batch, rng, deterministic=False):
        return bert.pretrain_loss(params, cfg, batch, rng=rng,
                                  deterministic=deterministic)

    return loss


def n_masked(traffic: Dict) -> int:
    return int(traffic["mask_rate"] * traffic["seq_len"]) + 1


def host_batches(model: Dict, traffic: Dict, batch_size: int, seed: int
                 ) -> Iterator[Dict[str, np.ndarray]]:
    """Distinct pre-training batches from the seed, on the host, in the
    program's gathered format: P = 15% of T + 1 masked positions a sequence
    (as BERT's max_predictions_per_seq), [MASK] written over them."""
    T, P = traffic["seq_len"], n_masked(traffic)
    rng = np.random.default_rng([int(seed), 0xBE87])
    rows = np.arange(batch_size)[:, None]
    while True:
        ids = rng.integers(0, model["vocab_size"], (batch_size, T),
                           dtype=np.int32)
        pos = np.sort(np.argpartition(
            rng.random((batch_size, T), dtype=np.float32), P, axis=1)[:, :P],
            axis=1).astype(np.int32)
        labels = ids[rows, pos]
        ids[rows, pos] = MASK_ID
        yield {"input_ids": ids,
               "token_type_ids": np.zeros((batch_size, T), np.int32),
               "masked_positions": pos,
               "masked_labels": labels,
               "nsp_labels": rng.integers(0, 2, (batch_size,),
                                          dtype=np.int32)}


def tokens_per_batch(traffic: Dict, batch_size: int) -> int:
    return batch_size * traffic["seq_len"]


def train_flops_per_token(model: Dict, traffic: Dict) -> float:
    return shapes.bert_train_flops_per_token(model, traffic["seq_len"],
                                             n_masked(traffic))


def reference_loss(params, model: Dict, batch, microbatch: int) -> float:
    return bert_ref.pretrain_loss(params, model, batch, microbatch)


def forward_gap(params, cfg, model: Dict, batch, n: int) -> float:
    """|program - reference| / |reference| (Frobenius) of the encoder's
    output on the first `n` sequences: the program's `bert.encode`, dropout
    off, against the plain float32 one."""
    import jax

    from paddle_tpu.models import bert

    ids, types = batch["input_ids"][:n], batch["token_type_ids"][:n]
    got = jax.jit(lambda p, i, t: bert.encode(p, cfg, i, t))(
        params, ids, types)
    want = bert_ref.encode_f32(params, model, ids, types)
    got = np.asarray(got, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
