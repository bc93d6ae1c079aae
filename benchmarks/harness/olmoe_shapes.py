"""Operations and bytes OLMoE's serving NEEDS, from its shapes alone (the
counts of `benchmarks/harness/shapes.py` are GPT-2's and BERT's). `cfg` is
the `model` group of a configuration file: `hidden`, `layers`,
`expert_dim`, `n_experts`, `top_k`, `vocab_size`."""

from __future__ import annotations

from typing import Dict


def expert_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * cfg["hidden"] * cfg["expert_dim"] * bytes_per_el


def router_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """The routers of all layers."""
    return cfg["layers"] * cfg["hidden"] * cfg["n_experts"] * bytes_per_el


def param_count(cfg: Dict) -> int:
    H, L = cfg["hidden"], cfg["layers"]
    per_layer = (4 * H * H + 4 * H + H * cfg["n_experts"]
                 + cfg["n_experts"] * 3 * H * cfg["expert_dim"])
    return L * per_layer + 2 * cfg["vocab_size"] * H + H


def dense_weight_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed: each layer's
    four attention matrices, four norm vectors and router, the final norm
    and the output head. The embedding gives a few rows only."""
    H, L = cfg["hidden"], cfg["layers"]
    return (L * (4 * H * H + 4 * H + H * cfg["n_experts"])
            + cfg["vocab_size"] * H + H) * bytes_per_el


def expected_experts_hit(cfg: Dict, rows: int) -> float:
    """Distinct experts of ONE layer that `rows` tokens select, each
    choosing `top_k` distinct of `n_experts` uniformly and independently:
    E (1 - (1 - k/E)^rows); 56.4 of 64 for 16 rows."""
    E, k = cfg["n_experts"], cfg["top_k"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    return 2 * cfg["layers"] * cfg["hidden"] * bytes_per_el


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 16,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the weights outside
    the experts once, the experts at the EXPECTED distinct count under
    uniform routing (`expected_experts_hit`: the step's own count is
    `experts_hit` of its record, which `moe_layer_min_bytes` takes; this
    one has to stand where no record does), and the K/V of the tokens
    resident in the live sequences. 16 slots is what the one OLMoE cell
    runs; the harness passes no slot count."""
    return (dense_weight_bytes(cfg, bytes_per_el)
            + cfg["layers"] * expected_experts_hit(cfg, slots)
            * expert_bytes(cfg, bytes_per_el)
            + live_tokens * kv_bytes_per_token(cfg, bytes_per_el))


def moe_layer_min_bytes(cfg: Dict, experts_hit: float,
                        bytes_per_el: int = 2) -> float:
    """Least bytes the expert layers of one decode step read: every
    selected expert's three matrices once (`experts_hit`: distinct experts
    summed over the layers) and the routers. Activations are a few rows."""
    return (experts_hit * expert_bytes(cfg, bytes_per_el)
            + router_bytes(cfg, bytes_per_el))
