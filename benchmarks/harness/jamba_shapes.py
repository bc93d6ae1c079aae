"""Operations and bytes Jamba's serving NEEDS, from its shapes alone
(`nemotron_h_shapes.py` holds Nemotron-H's, whose recurrence is Mamba-2's).
`cfg` is the `model` group of a configuration file: `hidden`, `n_layers`,
`attn_period`, `attn_offset`, `mlp_dim`, `expand`, `ssm_state`, `dt_rank`,
`conv_kernel`, `heads`, `kv_heads`, `head_dim`, `vocab_size`."""

from __future__ import annotations

from typing import Dict


def attention_layers(cfg: Dict) -> int:
    """Layers `l` with `l % attn_period == attn_offset`. 2 of 28."""
    return sum(l % cfg["attn_period"] == cfg["attn_offset"]
               for l in range(cfg["n_layers"]))


def mamba_layers(cfg: Dict) -> int:
    return cfg["n_layers"] - attention_layers(cfg)


def inner(cfg: Dict) -> int:
    return cfg["expand"] * cfg["hidden"]


def mamba_params(cfg: Dict) -> int:
    """One Mamba-1 mixer with its norm: the input and output projections,
    the projection to dt, B and C and dt's own, the convolution and its
    bias, `dt_bias`, `A_log`, `D`, the three inner norms, the layer's norm.
    41.25 M."""
    H, C = cfg["hidden"], inner(cfg)
    N, R = cfg["ssm_state"], cfg["dt_rank"]
    return (H * 2 * C + C * (R + 2 * N) + R * C + C * H
            + (cfg["conv_kernel"] + 1) * C + C + N * C + C
            + R + 2 * N + H)


def attention_params(cfg: Dict) -> int:
    """One attention mixer with its norm: q, k, v, o. 13.77 M."""
    H, d = cfg["hidden"], cfg["head_dim"]
    return 2 * H * cfg["heads"] * d + 2 * H * cfg["kv_heads"] * d + H


def mlp_params(cfg: Dict) -> int:
    """One dense SwiGLU with its norm. 62.92 M."""
    return 3 * cfg["hidden"] * cfg["mlp_dim"] + cfg["hidden"]


def top_params(cfg: Dict) -> int:
    """The embedding, which is the head too, and the final norm. 167.77 M."""
    return cfg["vocab_size"] * cfg["hidden"] + cfg["hidden"]


def param_count(cfg: Dict) -> int:
    """3.03 B at the published sizes."""
    return (mamba_layers(cfg) * mamba_params(cfg)
            + attention_layers(cfg) * attention_params(cfg)
            + cfg["n_layers"] * mlp_params(cfg) + top_params(cfg))


def state_row_bytes(cfg: Dict, conv_bytes_per_el: int = 2) -> int:
    """What ONE sequence keeps in ONE Mamba layer: the convolution's tail
    (3 x 5120 values in the served dtype, 30 KB) and the state (16 x 5120
    float32, 320 KB)."""
    return ((cfg["conv_kernel"] - 1) * inner(cfg) * conv_bytes_per_el
            + cfg["ssm_state"] * inner(cfg) * 4)


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What one cached token holds of the pools: K and V of the one K/V
    head in each ATTENTION layer. 1024 B."""
    return (attention_layers(cfg) * 2 * cfg["kv_heads"] * cfg["head_dim"]
            * bytes_per_el)


def ssm_step_min_bytes(cfg: Dict, slots: float, bytes_per_el: int = 2
                       ) -> float:
    """Least bytes the Mamba mixers of one decode step move (the scope
    `ssm`): their weights once, and the tail and the state of every one of
    the step's `slots` rows read and written once (the device computes idle
    rows too)."""
    return mamba_layers(cfg) * (
        (mamba_params(cfg) - cfg["hidden"]) * bytes_per_el
        + slots * 2 * state_row_bytes(cfg, bytes_per_el))


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 128,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: every weight once
    (the embedding is the head: all of it is read), the state of every row
    read and written once, and the K/V of the tokens resident in the live
    sequences. 128 slots is `serve.decode_slots` of the one configuration
    of this family; the harness passes no slot count."""
    return (param_count(cfg) * bytes_per_el
            + mamba_layers(cfg) * slots * 2 * state_row_bytes(cfg,
                                                              bytes_per_el)
            + live_tokens * kv_bytes_per_token(cfg, bytes_per_el))


def scan_min_flops(cfg: Dict, tokens: float) -> float:
    """Least multiply-adds x 2 of ONE Mamba mixer over a prompt of `tokens`:
    the four projections, the convolution, and the recurrence as written, a
    token at a time: a state value takes a product for its decay's
    exponent, the decay's product with the state, the input's product with
    B and its sum, and a product and a sum into y: 6 a value (the
    exponential itself is not counted)."""
    H, C = cfg["hidden"], inner(cfg)
    N, R = cfg["ssm_state"], cfg["dt_rank"]
    proj = 2 * (H * 2 * C + C * (R + 2 * N) + R * C + C * H)
    return tokens * (proj + 6 * N * C + 2 * cfg["conv_kernel"] * C)


def scan_min_bytes(cfg: Dict, tokens: float, bytes_per_el: int = 2) -> float:
    """Least bytes of ONE Mamba mixer over a prompt of `tokens`: its
    weights once, the mixer's input and output rows, and the state row
    written once."""
    return ((mamba_params(cfg) - cfg["hidden"]) * bytes_per_el
            + 2 * tokens * cfg["hidden"] * bytes_per_el
            + state_row_bytes(cfg, bytes_per_el))
