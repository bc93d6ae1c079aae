"""Operations and bytes Nemotron-H's serving NEEDS, from its shapes alone
(`shapes.py` holds GPT-2's and BERT's, `olmoe_shapes.py` OLMoE's,
`joyai_shapes.py` JoyAI's). `cfg` is the `model` group of a configuration
file: `hidden`, `pattern`, `ssm_heads`, `ssm_head_dim`, `ssm_groups`,
`ssm_state`, `conv_kernel`, `expert_dim`, `shared_dim`, `n_experts`,
`top_k`, `heads`, `kv_heads`, `head_dim`, `vocab_size`. Every count is at
the PUBLISHED widths: the routed experts are 1856 wide here, whatever the
program pads them to in its layout."""

from __future__ import annotations

from typing import Dict


def count(cfg: Dict, kind: str) -> int:
    """Blocks of `kind` ("M", "E", "*") in the pattern."""
    return cfg["pattern"].count(kind)


def inner(cfg: Dict) -> int:
    return cfg["ssm_heads"] * cfg["ssm_head_dim"]


def conv_dim(cfg: Dict) -> int:
    return inner(cfg) + 2 * cfg["ssm_groups"] * cfg["ssm_state"]


def mamba_params(cfg: Dict) -> int:
    """One Mamba block: the input and output projections, the convolution
    and its bias, dt_bias, A_log and D a head, the gated norm, the block's
    norm. 38.74 M."""
    H, nh = cfg["hidden"], cfg["ssm_heads"]
    return (H * (inner(cfg) + conv_dim(cfg) + nh)
            + (cfg["conv_kernel"] + 1) * conv_dim(cfg) + 3 * nh
            + inner(cfg) + inner(cfg) * H + H)


def expert_params(cfg: Dict) -> int:
    """One routed expert's up and down matrices. 9.98 M."""
    return 2 * cfg["hidden"] * cfg["expert_dim"]


def shared_params(cfg: Dict) -> int:
    return 2 * cfg["hidden"] * cfg["shared_dim"]


def router_params(cfg: Dict) -> int:
    """One expert block's router and correction bias."""
    return cfg["hidden"] * cfg["n_experts"] + cfg["n_experts"]


def moe_params(cfg: Dict) -> int:
    """One expert block, its norm included. 1297.47 M."""
    return (router_params(cfg) + cfg["n_experts"] * expert_params(cfg)
            + shared_params(cfg) + cfg["hidden"])


def attention_params(cfg: Dict) -> int:
    """One attention block: q, k, v, o and the block's norm. 23.40 M."""
    H, d = cfg["hidden"], cfg["head_dim"]
    return (2 * H * cfg["heads"] * d + 2 * H * cfg["kv_heads"] * d + H)


def top_params(cfg: Dict) -> int:
    """Embedding, head and the final norm. 704.65 M."""
    return 2 * cfg["vocab_size"] * cfg["hidden"] + cfg["hidden"]


def param_count(cfg: Dict) -> int:
    return (count(cfg, "M") * mamba_params(cfg)
            + count(cfg, "E") * moe_params(cfg)
            + count(cfg, "*") * attention_params(cfg) + top_params(cfg))


def active_params(cfg: Dict) -> int:
    """Parameters one token multiplies by: everything but the routed
    experts it is not given and the embedding rows it does not read."""
    return (param_count(cfg) - cfg["vocab_size"] * cfg["hidden"]
            - count(cfg, "E") * (cfg["n_experts"] - cfg["top_k"])
            * expert_params(cfg))


def expert_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """One routed expert. 19.96 MB in bf16."""
    return expert_params(cfg) * bytes_per_el


def state_row_bytes(cfg: Dict, conv_bytes_per_el: int = 2) -> int:
    """What ONE sequence keeps in ONE Mamba block: the convolution's tail
    (3 x 6144 values in the served dtype) and the SSM state (64 x 64 x 128
    float32 = 2.1 MB)."""
    return ((cfg["conv_kernel"] - 1) * conv_dim(cfg) * conv_bytes_per_el
            + inner(cfg) * cfg["ssm_state"] * 4)


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What one cached token holds of the pools: K and V of `kv_heads`
    heads in each ATTENTION block, nothing in the others. 1024 B at one
    attention block."""
    return (count(cfg, "*") * 2 * cfg["kv_heads"] * cfg["head_dim"]
            * bytes_per_el)


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever is routed: the Mamba and
    attention blocks, each expert block's norm, router and shared expert,
    the final norm and the output head. The embedding gives a few rows."""
    return (count(cfg, "M") * mamba_params(cfg)
            + count(cfg, "*") * attention_params(cfg)
            + count(cfg, "E") * (router_params(cfg) + shared_params(cfg)
                                 + cfg["hidden"])
            + cfg["vocab_size"] * cfg["hidden"]
            + cfg["hidden"]) * bytes_per_el


def expected_experts_hit(cfg: Dict, rows: int) -> float:
    """Distinct experts of ONE block that `rows` tokens select, each
    choosing `top_k` distinct of `n_experts` uniformly and independently:
    E (1 - (1 - k/E)^rows); 122.1 of 128 for 64 rows."""
    E, k = cfg["n_experts"], cfg["top_k"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def ssm_step_min_bytes(cfg: Dict, slots: int, bytes_per_el: int = 2
                       ) -> float:
    """Least bytes the Mamba blocks of one decode step move (the scope
    `ssm`): their weights once, and the state of every one of the step's
    `slots` rows read and written once (the device computes idle rows
    too)."""
    return count(cfg, "M") * (mamba_params(cfg) * bytes_per_el
                              + slots * 2 * state_row_bytes(cfg,
                                                            bytes_per_el))


def mlp_min_bytes(cfg: Dict, experts_hit: float, bytes_per_el: int = 2
                  ) -> float:
    """Least bytes the expert blocks of one decode step read (the scope
    `mlp`): each block's router and shared expert, and every selected
    routed expert's two matrices once (`experts_hit`: distinct experts
    summed over the blocks). Activations are a few rows."""
    return (count(cfg, "E") * (router_params(cfg) + shared_params(cfg))
            + experts_hit * expert_params(cfg)) * bytes_per_el


def attention_min_bytes(cfg: Dict, live_tokens: float,
                        bytes_per_el: int = 2) -> float:
    """Least bytes the attention of one decode step reads: every resident
    token's K and V once a block (a K/V head's 16 query heads share the
    one read)."""
    return live_tokens * kv_bytes_per_token(cfg, bytes_per_el)


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 64,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows: the weights outside
    the routed experts once, the routed experts at the EXPECTED distinct
    count under uniform routing (`expected_experts_hit`: the step's own
    count is `experts_hit` of its record, which `mlp_min_bytes` takes; this
    one has to stand where no record does), the state of every row read
    and written once, and the K/V of the tokens resident in the live
    sequences. 64 slots is `serve.decode_slots` of the one configuration
    of this family; the harness passes no slot count."""
    return (always_read_bytes(cfg, bytes_per_el)
            + count(cfg, "E") * expected_experts_hit(cfg, slots)
            * expert_bytes(cfg, bytes_per_el)
            + count(cfg, "M") * slots * 2 * state_row_bytes(cfg,
                                                            bytes_per_el)
            + live_tokens * kv_bytes_per_token(cfg, bytes_per_el))


def scan_min_flops(cfg: Dict, tokens: int) -> float:
    """Least multiply-adds x 2 of ONE Mamba block over a prompt of
    `tokens`: the input and output projections, and the recurrence as
    written, a token at a time: the outer product into the state and the
    state's product with C, 2 x 2 x heads x P x N a token (the chunked form
    does more: its quadratic part)."""
    H = cfg["hidden"]
    proj = 2 * H * (inner(cfg) + conv_dim(cfg) + cfg["ssm_heads"]) \
        + 2 * inner(cfg) * H
    return tokens * (proj + 4 * inner(cfg) * cfg["ssm_state"]
                     + 2 * cfg["conv_kernel"] * conv_dim(cfg))


def scan_min_bytes(cfg: Dict, tokens: int, bytes_per_el: int = 2) -> float:
    """Least bytes of ONE Mamba block over a prompt of `tokens`: its
    weights once, the block's input and output rows, and the state row
    written once."""
    return (mamba_params(cfg) * bytes_per_el
            + 2 * tokens * cfg["hidden"] * bytes_per_el
            + state_row_bytes(cfg, bytes_per_el))
