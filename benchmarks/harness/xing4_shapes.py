"""Operations and bytes Xing4.0-29B-A4B's serving NEEDS, from its shapes
alone. The block is JoyAI-LLM-Flash's (latent attention, leading dense
layers, sigmoid-routed experts with a shared one), so its counts are
`joyai_shapes.py`'s, read with this model's `model` group (the two share
their key names: `hidden`, `layers`, `dense_layers`, `heads`, `q_rank`,
`kv_rank`, `nope_dim`, `rope_dim`, `v_dim`, `dense_dim`, `expert_dim`,
`n_experts`, `top_k`, `vocab_size`); what this file adds is the residual
path: `hc_mult` streams a row, and two sets of maps a layer."""

from __future__ import annotations

from typing import Dict

from . import joyai_shapes
from .joyai_shapes import (expected_experts_hit, kv_bytes_per_token,  # noqa: F401
                           latent_attention_min_bytes, mlp_min_bytes)

SUB_LAYERS = 2      # attention and the second half, each with its own maps


def carried_lanes(cfg: Dict) -> int:
    """Values a row carries from block to block: `hc_mult` streams."""
    return cfg["hc_mult"] * cfg["hidden"]


def map_params(cfg: Dict) -> int:
    """One sub-layer's maps: Phi `[n C, 2n + n^2]`, the three scalars,
    `b_pre`, `b_post` `[n]` and `b_res` `[n, n]`."""
    n = cfg["hc_mult"]
    return carried_lanes(cfg) * (2 * n + n * n) + 3 + 2 * n + n * n


def param_count(cfg: Dict) -> int:
    return joyai_shapes.param_count(cfg) \
        + cfg["layers"] * SUB_LAYERS * map_params(cfg)


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed: the shared
    block's (`joyai_shapes.always_read_bytes`) and every sub-layer's
    maps."""
    return joyai_shapes.always_read_bytes(cfg, bytes_per_el) \
        + cfg["layers"] * SUB_LAYERS * map_params(cfg) * bytes_per_el


def mhc_min_bytes(cfg: Dict, rows: int, bytes_per_el: int = 2) -> float:
    """Least bytes the residual path of one decode step of `rows` rows
    moves: a sub-layer's maps once, and the rows' streams read once and
    written once a sub-layer (the sub-layer's own input and output, one
    stream wide, are its own)."""
    per_sub_layer = (map_params(cfg)
                     + 2 * rows * carried_lanes(cfg)) * bytes_per_el
    return float(cfg["layers"] * SUB_LAYERS * per_sub_layer)


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 32,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the shared block's
    count (weights outside the routed experts once, the routed experts at
    the expected distinct count under uniform routing, the latent cache of
    the resident tokens) and the residual path's (`mhc_min_bytes`). 32
    slots is what the one cell of this configuration runs; the harness
    passes no slot count."""
    return joyai_shapes.decode_step_min_bytes(
        cfg, live_tokens, slots, bytes_per_el) \
        + mhc_min_bytes(cfg, slots, bytes_per_el)
