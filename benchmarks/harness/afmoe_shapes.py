"""Operations and bytes Trinity's (`afmoe`) serving NEEDS, from its shapes
alone. `cfg` is the `model` group of a configuration file: `hidden`,
`pattern` (a LAYER a character, `W` a sliding-window layer, `*` a full one;
every layer is attention then an MLP between two norms each), `heads`,
`kv_heads`, `head_dim`, `window`, `dense_layers`, `dense_dim`, `expert_dim`,
`n_experts` (the router's width), `top_k`, `held` (first, past the last of
the routed experts this chip holds), `vocab_size`."""

from __future__ import annotations

from typing import Dict


def layers(cfg: Dict) -> int:
    return len(cfg["pattern"])


def count(cfg: Dict, kind: str) -> int:
    """Layers of `kind`: `W` (the window kind's cache) or `*` (global)."""
    return cfg["pattern"].count(kind)


def expert_layers(cfg: Dict) -> int:
    return layers(cfg) - cfg["dense_layers"]


def held_experts(cfg: Dict) -> int:
    first, past = cfg.get("held") or (0, cfg["n_experts"])
    return past - first


def attention_params(cfg: Dict) -> int:
    """q, the output gate and o at `heads x head_dim`, k and v at `kv_heads
    x head_dim`, the two per-head gains. 27,263,488."""
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    return cfg["hidden"] * (3 * q + 2 * kv) + 2 * cfg["head_dim"]


def norm_params(cfg: Dict) -> int:
    """A layer's four norms. 8,192."""
    return 4 * cfg["hidden"]


def dense_mlp_params(cfg: Dict) -> int:
    """A leading layer's MLP. 37,748,736."""
    return 3 * cfg["hidden"] * cfg["dense_dim"]


def expert_params(cfg: Dict) -> int:
    """One routed expert's three matrices, and the shared expert's.
    6,291,456."""
    return 3 * cfg["hidden"] * cfg["expert_dim"]


def router_params(cfg: Dict) -> int:
    """The router over ALL the routed experts and the selection bias.
    262,272."""
    return (cfg["hidden"] + 1) * cfg["n_experts"]


def top_params(cfg: Dict) -> int:
    """Embedding, untied head, final norm."""
    return 2 * cfg["vocab_size"] * cfg["hidden"] + cfg["hidden"]


def param_count(cfg: Dict) -> int:
    """3158.9 M at 8 layers, 64 of 128 held, half the vocabulary."""
    return (layers(cfg) * (attention_params(cfg) + norm_params(cfg))
            + cfg["dense_layers"] * dense_mlp_params(cfg)
            + expert_layers(cfg) * (router_params(cfg)
                                    + (1 + held_experts(cfg))
                                    * expert_params(cfg))
            + top_params(cfg))


def token_layer_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one token in ONE layer. 2048 B."""
    return 2 * cfg["kv_heads"] * cfg["head_dim"] * bytes_per_el


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What a token keeps for ever: the GLOBAL kind's layers (the harness
    multiplies it by the pool's tokens, `num_blocks`' kind). 4096 B."""
    return count(cfg, "*") * token_layer_bytes(cfg, bytes_per_el)


def attention_min_bytes(cfg: Dict, live_tokens: float, bytes_per_el: int = 2
                        ) -> float:
    """Least bytes the full layers' walks read a step: every resident
    token's K and V once a full layer."""
    return live_tokens * kv_bytes_per_token(cfg, bytes_per_el)


def window_min_bytes(cfg: Dict, window_tokens: float, bytes_per_el: int = 2
                     ) -> float:
    """Least bytes the sliding layers' walks read a step: the keys the
    windows hold (`window_tokens` of the step record: sum over the slots of
    min(position + 1, window)) once a sliding layer."""
    return window_tokens * count(cfg, "W") * token_layer_bytes(
        cfg, bytes_per_el)


def expected_experts_hit(cfg: Dict, rows: int) -> float:
    """Distinct HELD experts of one layer that `rows` tokens select, each
    choosing `top_k` distinct of `n_experts` uniformly: 55.9 of 64 for 32
    rows."""
    E, k = cfg["n_experts"], cfg["top_k"]
    return held_experts(cfg) * (1.0 - (1.0 - k / E) ** rows)


def mlp_min_bytes(cfg: Dict, experts_hit: float, bytes_per_el: int = 2
                  ) -> float:
    """Least bytes the MLPs of one decode step read (the scope `mlp`): the
    leading layers' dense MLPs, each expert layer's router and shared
    expert, and every selected HELD expert's three matrices once
    (`experts_hit`: distinct held experts summed over the layers, the step
    record's count; 12.6 MB each)."""
    return (cfg["dense_layers"] * dense_mlp_params(cfg)
            + expert_layers(cfg) * (router_params(cfg) + expert_params(cfg))
            + experts_hit * expert_params(cfg)) * bytes_per_el


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed. The embedding is
    read a row a slot, not whole."""
    return (param_count(cfg) - cfg["vocab_size"] * cfg["hidden"]
            - expert_layers(cfg) * held_experts(cfg) * expert_params(cfg)
            ) * bytes_per_el


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 32,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the weights outside
    the routed experts once, the held experts at the EXPECTED distinct count
    under uniform routing, the full layers' K/V of the tokens resident, and
    the sliding layers' windows BY SLOT (a window a slot, full: the harness
    hands the live tokens alone, and a cell of this family decodes far past
    the window). 32 slots is `serve.decode_slots` of the one configuration
    of this family."""
    return (always_read_bytes(cfg, bytes_per_el)
            + expert_layers(cfg) * expected_experts_hit(cfg, slots)
            * expert_params(cfg) * bytes_per_el
            + attention_min_bytes(cfg, live_tokens, bytes_per_el)
            + window_min_bytes(cfg, slots * cfg["window"], bytes_per_el))
