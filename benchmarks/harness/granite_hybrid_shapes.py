"""Operations and bytes Granite 4.0-H's serving NEEDS, from its shapes alone
(`nemotron_h_shapes.py` holds Nemotron-H's, `longcat_shapes.py` LongCat's).
`cfg` is the `model` group of a configuration file: `hidden`, `pattern` (a
LAYER a character, `M` a Mamba-2 mixer, `*` attention; every layer is its
mixer then experts), `ssm_heads`, `ssm_head_dim`, `ssm_groups`, `ssm_state`,
`conv_kernel`, `expert_dim`, `shared_dim`, `n_experts` (the router's width),
`top_k`, `held` (first, past the last of the routed experts this chip
holds), `heads`, `kv_heads`, `head_dim`, `vocab_size`. The Mamba mixer's and
the attention's counts are `nemotron_h_shapes`' under the same key names."""

from __future__ import annotations

from typing import Dict

from . import nemotron_h_shapes as _nh

# one Mamba mixer with its norm; one sequence's row of one Mamba layer; K
# and V of a token over the attention layers; the attention's bytes a step
mamba_params = _nh.mamba_params
state_row_bytes = _nh.state_row_bytes
kv_bytes_per_token = _nh.kv_bytes_per_token
attention_min_bytes = _nh.attention_min_bytes
ssm_step_min_bytes = _nh.ssm_step_min_bytes
attention_params = _nh.attention_params


def layers(cfg: Dict) -> int:
    return len(cfg["pattern"])


def held_experts(cfg: Dict) -> int:
    first, past = cfg.get("held") or (0, cfg["n_experts"])
    return past - first


def expert_params(cfg: Dict) -> int:
    """One routed expert's gate, up and down matrices. 9.44 M."""
    return 3 * cfg["hidden"] * cfg["expert_dim"]


def shared_params(cfg: Dict) -> int:
    """The shared expert's three matrices. 18.87 M."""
    return 3 * cfg["hidden"] * cfg["shared_dim"]


def router_params(cfg: Dict) -> int:
    """The router over ALL the routed experts, held or not. 0.29 M."""
    return cfg["hidden"] * cfg["n_experts"]


def moe_params(cfg: Dict) -> int:
    """One layer's experts as held here, their norm included. 358.91 M."""
    return (router_params(cfg) + held_experts(cfg) * expert_params(cfg)
            + shared_params(cfg) + cfg["hidden"])


def top_params(cfg: Dict) -> int:
    """The embedding, which is the head too, and the final norm."""
    return cfg["vocab_size"] * cfg["hidden"] + cfg["hidden"]


def param_count(cfg: Dict) -> int:
    """4757.2 M at one period of the pattern, 36 held, half the
    vocabulary."""
    return (_nh.count(cfg, "M") * mamba_params(cfg)
            + _nh.count(cfg, "*") * attention_params(cfg)
            + layers(cfg) * moe_params(cfg) + top_params(cfg))


def expert_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """One routed expert. 18.87 MB in bf16."""
    return expert_params(cfg) * bytes_per_el


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed: the mixers, each
    layer's expert norm, router and shared expert, the final norm and the
    tied head."""
    return (param_count(cfg) - layers(cfg) * held_experts(cfg)
            * expert_params(cfg)) * bytes_per_el


def expected_experts_hit(cfg: Dict, rows: int) -> float:
    """Distinct HELD experts of one layer that `rows` tokens select, each
    choosing `top_k` distinct of `n_experts` uniformly: held x (1 - (1 -
    k/E)^rows); 35.97 of 36 for 48 rows."""
    E, k = cfg["n_experts"], cfg["top_k"]
    return held_experts(cfg) * (1.0 - (1.0 - k / E) ** rows)


def mlp_min_bytes(cfg: Dict, experts_hit: float, bytes_per_el: int = 2
                  ) -> float:
    """Least bytes the expert layers of one decode step read (the scope
    `mlp`): each layer's router and shared expert, and every selected HELD
    expert's three matrices once (`experts_hit`: distinct held experts
    summed over the layers, the step record's count)."""
    return (layers(cfg) * (router_params(cfg) + shared_params(cfg))
            + experts_hit * expert_params(cfg)) * bytes_per_el


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 48,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the weights outside
    the routed experts once, the held experts at the EXPECTED distinct
    count under uniform routing, the state of every row read and written
    once (the device computes idle rows too), and the K/V of the tokens
    resident. 48 slots is `serve.decode_slots` of the one configuration of
    this family; the harness passes no slot count."""
    return (always_read_bytes(cfg, bytes_per_el)
            + layers(cfg) * expected_experts_hit(cfg, slots)
            * expert_bytes(cfg, bytes_per_el)
            + _nh.count(cfg, "M") * slots * 2
            * state_row_bytes(cfg, bytes_per_el)
            + live_tokens * kv_bytes_per_token(cfg, bytes_per_el))
