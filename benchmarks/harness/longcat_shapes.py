"""Operations and bytes LongCat-Flash-Chat's serving NEEDS, from its shapes
alone. `cfg` is the `model` group of a configuration file: `hidden`,
`layers` (each of TWO attention sub-layers and two dense MLPs), `heads`,
`q_rank`, `kv_rank`, `nope_dim`, `rope_dim`, `v_dim`, `dense_dim`,
`expert_dim`, `n_experts` (the router's routed outputs), `zero_experts`
(its zero-compute outputs, which have no weights), `top_k`, `held` (first,
past the last of the routed experts whose matrices are HERE; null: all),
`vocab_size` (this chip's slice)."""

from __future__ import annotations

from typing import Dict, Optional

# lanes of the pool that holds the rotary key: the TPU tiles lanes by 128,
# so the 64 values a token stores there take a whole tile
ROPE_LANES = 128
SUB_BLOCKS = 2      # attention sub-layers (and dense MLPs) a layer


def attention_params(cfg: Dict) -> int:
    """One latent attention sub-layer: W_qa, W_qb, W_kva, W_kvb, W_o and
    the two inner norms."""
    H, nh = cfg["hidden"], cfg["heads"]
    qk = cfg["nope_dim"] + cfg["rope_dim"]
    return (H * cfg["q_rank"] + cfg["q_rank"]
            + cfg["q_rank"] * nh * qk
            + H * (cfg["kv_rank"] + cfg["rope_dim"]) + cfg["kv_rank"]
            + cfg["kv_rank"] * nh * (cfg["nope_dim"] + cfg["v_dim"])
            + nh * cfg["v_dim"] * H)


def dense_mlp_params(cfg: Dict) -> int:
    """One dense SwiGLU: gate, up and down."""
    return 3 * cfg["hidden"] * cfg["dense_dim"]


def expert_params(cfg: Dict) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * cfg["hidden"] * cfg["expert_dim"]


def expert_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    return expert_params(cfg) * bytes_per_el


def router_outputs(cfg: Dict) -> int:
    return cfg["n_experts"] + cfg["zero_experts"]


def router_params(cfg: Dict) -> int:
    """A layer's router and correction bias, over ALL its outputs."""
    return (cfg["hidden"] + 1) * router_outputs(cfg)


def held_experts(cfg: Dict) -> int:
    held = cfg.get("held")
    return cfg["n_experts"] if held is None else held[1] - held[0]


def outside_experts_params(cfg: Dict) -> int:
    """A layer outside its routed experts: two attention sub-layers with
    their block norms, two dense MLPs, the router."""
    return SUB_BLOCKS * (attention_params(cfg) + 2 * cfg["hidden"]
                         + dense_mlp_params(cfg)) + router_params(cfg)


def layer_params(cfg: Dict) -> int:
    """A layer as this chip holds it: with the experts HELD here."""
    return outside_experts_params(cfg) + held_experts(cfg) \
        * expert_params(cfg)


def param_count(cfg: Dict) -> int:
    H = cfg["hidden"]
    return cfg["layers"] * layer_params(cfg) + 2 * cfg["vocab_size"] * H + H


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed: everything of
    the layers outside the routed experts, the final norm and the head.
    The embedding gives a few rows only."""
    H = cfg["hidden"]
    return (cfg["layers"] * outside_experts_params(cfg)
            + cfg["vocab_size"] * H + H) * bytes_per_el


def expected_experts_hit(cfg: Dict, rows: int) -> float:
    """Distinct HELD experts of ONE layer that `rows` tokens select, each
    choosing `top_k` distinct of the router's outputs uniformly and
    independently: held (1 - (1 - k/outputs)^rows); 13.9 of 16 for 128
    rows."""
    return held_experts(cfg) * (
        1.0 - (1.0 - cfg["top_k"] / router_outputs(cfg)) ** rows)


def kv_content_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What attention must READ of one cached token, all cache layers (two
    a layer): the compressed vector and the rotary key, 512 + 64 values."""
    return SUB_BLOCKS * cfg["layers"] \
        * (cfg["kv_rank"] + cfg["rope_dim"]) * bytes_per_el


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What one cached token HOLDS of the pools, all cache layers: the
    rotary key lies in a pool of whole lane tiles."""
    rope = -(-cfg["rope_dim"] // ROPE_LANES) * ROPE_LANES
    return SUB_BLOCKS * cfg["layers"] * (cfg["kv_rank"] + rope) \
        * bytes_per_el


def shortcut_min_bytes(cfg: Dict, experts_hit: float,
                       bytes_per_el: int = 2) -> float:
    """Least bytes the expert path (the scope `shortcut_experts`) of a
    decode step reads: every layer's router, and every selected HELD
    expert's three matrices once (`experts_hit`: distinct held experts
    summed over the layers). A zero-compute expert reads nothing;
    activations are a few rows."""
    return (cfg["layers"] * router_params(cfg)
            + experts_hit * expert_params(cfg)) * bytes_per_el


def dense_mlp_min_bytes(cfg: Dict, bytes_per_el: int = 2) -> float:
    """Least bytes the dense MLPs (the scope `mlp`) of a decode step read:
    gate, up and down of both sub-blocks of every layer once."""
    return float(cfg["layers"] * SUB_BLOCKS * dense_mlp_params(cfg)
                 * bytes_per_el)


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 128,
                          experts_hit: Optional[float] = None,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the weights outside
    the routed experts once, the held experts a step selects (`experts_hit`
    summed over the layers: the step records' own count, or where there is
    none the EXPECTED distinct count under uniform routing), and the cached
    content of the tokens resident in the live sequences. 128 slots is what
    the one cell of this configuration runs; the harness passes no slot
    count."""
    if experts_hit is None:
        experts_hit = cfg["layers"] * expected_experts_hit(cfg, slots)
    return (always_read_bytes(cfg, bytes_per_el)
            + experts_hit * expert_bytes(cfg, bytes_per_el)
            + live_tokens * kv_content_bytes_per_token(cfg, bytes_per_el))


def latent_attention_min_bytes(cfg: Dict, live_tokens: float,
                               bytes_per_el: int = 2) -> float:
    """Least bytes the latent attention of one decode step reads: every
    resident token's compressed vector and rotary key once a cache layer
    (all 64 heads share the one row), plus the key and value halves of
    W_kvb that the absorbed form multiplies by, a sub-layer."""
    w_kvb = cfg["kv_rank"] * cfg["heads"] * (cfg["nope_dim"] + cfg["v_dim"])
    return (live_tokens * kv_content_bytes_per_token(cfg, bytes_per_el)
            + SUB_BLOCKS * cfg["layers"] * w_kvb * bytes_per_el)


def latent_attention_flops(cfg: Dict, live_tokens: float, slots: int = 128
                           ) -> float:
    """Multiply-adds x 2 of the absorbed attention of one decode step: a
    cached token meets every head's query over 512 + 64 lanes and gives its
    512 lanes to every head's context (2 x 64 x (576 + 512) = 139 kFLOP a
    token a cache layer, against 1152 B read: 121 FLOP/B, half the v5e's
    ridge of 240, so memory still binds, by a factor of two where 32 heads
    have four), and a row's W_UK and W_UV products."""
    nh, rank = cfg["heads"], cfg["kv_rank"]
    per_token = 2 * nh * (rank + cfg["rope_dim"] + rank)
    absorb = 2 * nh * rank * (cfg["nope_dim"] + cfg["v_dim"])
    return SUB_BLOCKS * cfg["layers"] * (live_tokens * per_token
                                         + slots * absorb)
