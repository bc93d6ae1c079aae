"""Operations and bytes an algorithm NEEDS, from its shapes alone. Copies of
`BertConfig.train_flops_per_seq` / `GPTConfig` arithmetic, kept here so that
no later change to the program moves the yardstick. `cfg` is the `model`
group of a configuration file."""

from __future__ import annotations

from typing import Dict


def bert_train_flops_per_token(cfg: Dict, seq_len: int, n_masked: int
                               ) -> float:
    """Forward + backward matmul FLOPs per trained token of BERT MLM+NSP:
    3 x forward; forward = 2 x tokens x matmul parameters + the attention
    score/context products + the vocabulary projection on the masked
    positions only. Recomputed operations do not count."""
    H, M, L = cfg["hidden"], cfg["mlp_dim"], cfg["layers"]
    matmul_params = L * (4 * H * H + 2 * H * M) + 2 * H * H
    fwd = (2 * seq_len * matmul_params
           + L * 4 * seq_len * seq_len * H
           + 2 * n_masked * cfg["vocab_size"] * H)
    return 3.0 * fwd / seq_len


def gpt_param_count(cfg: Dict) -> int:
    H, M, L = cfg["hidden"], cfg["mlp_dim"], cfg["layers"]
    per_layer = (4 * H * H + 4 * H) + (2 * H * M + M + H) + 4 * H
    return (L * per_layer + cfg["vocab_size"] * H + cfg["max_len"] * H
            + 2 * H)


def gpt_decode_weight_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights one decode step must read once: every layer's matrices and
    vectors, the final LayerNorm and the tied output embedding. The position
    table and the embedding rows of the step's tokens are a few rows only."""
    H = cfg["hidden"]
    return (gpt_param_count(cfg) - cfg["max_len"] * H) * bytes_per_el


def gpt_kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    return 2 * cfg["layers"] * cfg["hidden"] * bytes_per_el


def gpt_decode_step_min_bytes(cfg: Dict, live_tokens: float,
                              bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step: the weights once plus the K/V of the
    tokens resident in the live sequences (read once; the one new row per
    sequence written is negligible beside them)."""
    return (gpt_decode_weight_bytes(cfg, bytes_per_el)
            + live_tokens * gpt_kv_bytes_per_token(cfg, bytes_per_el))
