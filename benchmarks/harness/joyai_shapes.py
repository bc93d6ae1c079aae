"""Operations and bytes JoyAI-LLM-Flash's serving NEEDS, from its shapes
alone (`shapes.py` holds GPT-2's and BERT's, `olmoe_shapes.py` OLMoE's).
`cfg` is the `model` group of a configuration file: `hidden`, `layers`,
`dense_layers`, `heads`, `q_rank`, `kv_rank`, `nope_dim`, `rope_dim`,
`v_dim`, `dense_dim`, `expert_dim`, `n_experts`, `top_k`, `vocab_size`."""

from __future__ import annotations

from typing import Dict

# lanes of the pool that holds the rotary key: the TPU tiles lanes by 128,
# so the 64 values a token stores there take a whole tile
ROPE_LANES = 128


def attention_params(cfg: Dict) -> int:
    """One layer's latent attention: W_qa, W_qb, W_kva, W_kvb, W_o and the
    two inner norms."""
    H, nh = cfg["hidden"], cfg["heads"]
    qk = cfg["nope_dim"] + cfg["rope_dim"]
    return (H * cfg["q_rank"] + cfg["q_rank"]
            + cfg["q_rank"] * nh * qk
            + H * (cfg["kv_rank"] + cfg["rope_dim"]) + cfg["kv_rank"]
            + cfg["kv_rank"] * nh * (cfg["nope_dim"] + cfg["v_dim"])
            + nh * cfg["v_dim"] * H)


def expert_params(cfg: Dict) -> int:
    """One expert's (routed or shared) gate, up and down matrices."""
    return 3 * cfg["hidden"] * cfg["expert_dim"]


def expert_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    return expert_params(cfg) * bytes_per_el


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden"] * cfg["dense_dim"]


def router_params(cfg: Dict) -> int:
    """One expert layer's router and correction bias."""
    return cfg["hidden"] * cfg["n_experts"] + cfg["n_experts"]


def param_count(cfg: Dict) -> int:
    H = cfg["hidden"]
    dense = cfg["dense_layers"]
    sparse = cfg["layers"] - dense
    per_layer = attention_params(cfg) + 2 * H       # + the block's two norms
    return (cfg["layers"] * per_layer + dense * dense_mlp_params(cfg)
            + sparse * (router_params(cfg)
                        + (cfg["n_experts"] + 1) * expert_params(cfg))
            + 2 * cfg["vocab_size"] * H + H)


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed: each layer's
    attention and norms, the dense layers' MLP, each expert layer's router
    and shared expert, the final norm and the output head. The embedding
    gives a few rows only."""
    H = cfg["hidden"]
    dense = cfg["dense_layers"]
    sparse = cfg["layers"] - dense
    return (cfg["layers"] * (attention_params(cfg) + 2 * H)
            + dense * dense_mlp_params(cfg)
            + sparse * (router_params(cfg) + expert_params(cfg))
            + cfg["vocab_size"] * H + H) * bytes_per_el


def expected_experts_hit(cfg: Dict, rows: int) -> float:
    """Distinct experts of ONE layer that `rows` tokens select, each
    choosing `top_k` distinct of `n_experts` uniformly and independently:
    E (1 - (1 - k/E)^rows); 163.3 of 256 for 32 rows."""
    E, k = cfg["n_experts"], cfg["top_k"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def kv_content_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What attention must READ of one cached token, all layers: the
    compressed vector and the rotary key, 512 + 64 values a layer."""
    return cfg["layers"] * (cfg["kv_rank"] + cfg["rope_dim"]) * bytes_per_el


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What one cached token HOLDS of the pools, all layers: the rotary key
    lies in a pool of whole lane tiles (paddle_tpu/serving/kv_cache.py)."""
    rope = -(-cfg["rope_dim"] // ROPE_LANES) * ROPE_LANES
    return cfg["layers"] * (cfg["kv_rank"] + rope) * bytes_per_el


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 32,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the weights outside
    the routed experts once, the routed experts at the EXPECTED distinct
    count under uniform routing (`expected_experts_hit`: the step's own
    count is `experts_hit` of its record, which `mlp_min_bytes` takes; this
    one has to stand where no record does), and the cached content of the
    tokens resident in the live sequences. 32 slots is what the one cell of
    this configuration runs; the harness passes no slot count."""
    sparse = cfg["layers"] - cfg["dense_layers"]
    return (always_read_bytes(cfg, bytes_per_el)
            + sparse * expected_experts_hit(cfg, slots)
            * expert_bytes(cfg, bytes_per_el)
            + live_tokens * kv_content_bytes_per_token(cfg, bytes_per_el))


def mlp_min_bytes(cfg: Dict, experts_hit: float, bytes_per_el: int = 2
                  ) -> float:
    """Least bytes the second halves of a decode step's blocks (the scope
    `mlp`) read: the dense layers' three matrices, each expert layer's
    router and shared expert, and every selected routed expert's three
    matrices once (`experts_hit`: distinct experts summed over the layers).
    Activations are a few rows."""
    dense = cfg["dense_layers"]
    sparse = cfg["layers"] - dense
    return (dense * dense_mlp_params(cfg)
            + sparse * (router_params(cfg) + expert_params(cfg))
            + experts_hit * expert_params(cfg)) * bytes_per_el


def latent_attention_min_bytes(cfg: Dict, live_tokens: float,
                               bytes_per_el: int = 2) -> float:
    """Least bytes the latent attention of one decode step reads: every
    resident token's compressed vector and rotary key once a layer (all
    heads share the one row), plus the key and value halves of W_kvb that
    the absorbed form multiplies by, a layer."""
    w_kvb = cfg["kv_rank"] * cfg["heads"] * (cfg["nope_dim"] + cfg["v_dim"])
    return (live_tokens * kv_content_bytes_per_token(cfg, bytes_per_el)
            + cfg["layers"] * w_kvb * bytes_per_el)


def latent_attention_flops(cfg: Dict, live_tokens: float, slots: int = 32
                           ) -> float:
    """Multiply-adds x 2 of the absorbed attention of one decode step: a
    cached token meets every head's query over 512 + 64 lanes and gives its
    512 lanes to every head's context (2 x 32 x (576 + 512) = 69.6 kFLOP a
    token a layer, against 1152 B read: 60 FLOP/B, under the v5e's ridge of
    240, so memory binds), and a row's W_UK and W_UV products."""
    nh, rank = cfg["heads"], cfg["kv_rank"]
    per_token = 2 * nh * (rank + cfg["rope_dim"] + rank)
    absorb = 2 * nh * rank * (cfg["nope_dim"] + cfg["v_dim"])
    return cfg["layers"] * (live_tokens * per_token + slots * absorb)
