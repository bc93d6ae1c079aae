"""Operations and bytes MiniCPM-SALA's serving NEEDS, from its shapes alone
(`shapes.py` holds GPT-2's and BERT's, `olmoe_shapes.py`, `joyai_shapes.py`
and `nemotron_h_shapes.py` the others'). `cfg` is the `model` group of a
configuration file: `hidden`, `mlp_dim`, `mixers` (a character a layer: `S`
block-sparse attention, `L` lightning attention), `heads`, `kv_heads`,
`head_dim`, `kernel_size`, `kernel_stride`, `sel_block`, `topk`,
`dense_len`, `lin_heads`, `lin_head_dim`, `vocab_size`."""

from __future__ import annotations

from typing import Dict

STATE_BYTES_PER_EL = 4      # the lightning state is float32


def count(cfg: Dict, kind: str) -> int:
    """Layers of `kind` ("S" sparse, "L" lightning)."""
    return cfg["mixers"].count(kind)


def swiglu_params(cfg: Dict) -> int:
    """One layer's SwiGLU: gate, up and down. 201.3 M."""
    return 3 * cfg["hidden"] * cfg["mlp_dim"]


def sparse_mixer_params(cfg: Dict) -> int:
    """A sparse layer's mixer: q, k, v, the output gate, o and the QK-norm
    gains. 52.4 M."""
    H, q = cfg["hidden"], cfg["heads"] * cfg["head_dim"]
    kv = cfg["kv_heads"] * cfg["head_dim"]
    return H * (3 * q + 2 * kv) + 2 * cfg["head_dim"]


def lightning_mixer_params(cfg: Dict) -> int:
    """A lightning layer's mixer: q, k, v, the output gate, o, the QK-norm
    gains and the output norm. 83.9 M."""
    H, w = cfg["hidden"], cfg["lin_heads"] * cfg["lin_head_dim"]
    return 5 * H * w + 2 * cfg["lin_head_dim"] + w


def sparse_layer_params(cfg: Dict) -> int:
    """Mixer, SwiGLU and the two norms. 253.8 M."""
    return sparse_mixer_params(cfg) + swiglu_params(cfg) + 2 * cfg["hidden"]


def lightning_layer_params(cfg: Dict) -> int:
    """285.2 M."""
    return (lightning_mixer_params(cfg) + swiglu_params(cfg)
            + 2 * cfg["hidden"])


def top_params(cfg: Dict) -> int:
    """Embedding, head and the final norm. 601.7 M."""
    return 2 * cfg["vocab_size"] * cfg["hidden"] + cfg["hidden"]


def param_count(cfg: Dict) -> int:
    return (count(cfg, "S") * sparse_layer_params(cfg)
            + count(cfg, "L") * lightning_layer_params(cfg)
            + top_params(cfg))


def always_read_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """Weights every decode step reads: every layer, the final norm and the
    head; the embedding gives a few rows."""
    return (param_count(cfg) - cfg["vocab_size"] * cfg["hidden"]) \
        * bytes_per_el


def state_row_bytes(cfg: Dict) -> int:
    """What ONE sequence keeps in ONE lightning layer: `heads x D x D`
    float32, 2 097 152 B."""
    return cfg["lin_heads"] * cfg["lin_head_dim"] ** 2 * STATE_BYTES_PER_EL


def kv_row_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one token in one sparse layer: 1024 B."""
    return 2 * cfg["kv_heads"] * cfg["head_dim"] * bytes_per_el


def kc_entry_bytes(cfg: Dict, bytes_per_el: int = 2) -> int:
    """One compressed key (all K/V heads) in one sparse layer: 512 B."""
    return cfg["kv_heads"] * cfg["head_dim"] * bytes_per_el


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What one cached token holds of the block pools, EVERY stored entry:
    K and V a sparse layer and a compressed key's share, one every
    `kernel_stride` tokens. 2 x (1024 + 32) = 2112 B."""
    return count(cfg, "S") * (
        kv_row_bytes(cfg, bytes_per_el)
        + kc_entry_bytes(cfg, bytes_per_el) // cfg["kernel_stride"])


def taken_tokens(cfg: Dict, blocks: float) -> float:
    """Tokens of `blocks` taken blocks: all whole but the newest, which is
    half full on average."""
    return max(blocks - 0.5, 0.0) * cfg["sel_block"]


def sparse_attention_min_bytes(cfg: Dict, kc_entries: float,
                               sparse_rows: float, blocks: float,
                               dense_tokens: float, bytes_per_el: int = 2
                               ) -> float:
    """Least bytes the sparse layers of one decode step read: the
    compressed keys the sparse rows score (`kc_entries`: complete windows
    summed over them), the K and V of the blocks they take (`blocks` a
    row), and every token of the rows that read everything."""
    return count(cfg, "S") * (
        kc_entries * kc_entry_bytes(cfg, bytes_per_el)
        + (sparse_rows * taken_tokens(cfg, blocks) + dense_tokens)
        * kv_row_bytes(cfg, bytes_per_el))


def linear_state_min_bytes(cfg: Dict, slots: float, bytes_per_el: int = 2
                           ) -> float:
    """Least bytes the lightning layers of one decode step move (the scope
    `ssm`): their mixers' weights once, and the state of every one of the
    step's `slots` rows read and written once (the device computes idle
    rows too)."""
    return count(cfg, "L") * (
        lightning_mixer_params(cfg) * bytes_per_el
        + slots * 2 * state_row_bytes(cfg))


def dense_mlp_min_bytes(cfg: Dict, bytes_per_el: int = 2) -> float:
    """Least bytes the SwiGLUs of one decode step read (the scope `mlp`):
    their weights once; activations are a few rows."""
    return len(cfg["mixers"]) * swiglu_params(cfg) * bytes_per_el


def decode_step_min_bytes(cfg: Dict, live_tokens: float, slots: int = 32,
                          bytes_per_el: int = 2) -> float:
    """Least bytes of one decode step of `slots` rows holding `live_tokens`
    between them (each taken to hold the mean): the weights once, every
    row's lightning state read and written once, and what the sparse
    layers read of a row: compressed keys and `topk` blocks over
    `dense_len` tokens, everything at or under it. 32 slots is
    `serve.decode_slots` of the one configuration of this family; the
    harness passes no slot count."""
    n = live_tokens / slots
    if n > cfg["dense_len"]:
        attn = sparse_attention_min_bytes(
            cfg, slots * (n // cfg["kernel_stride"] - 1), slots,
            min(cfg["topk"], n // cfg["sel_block"] + 1), 0.0, bytes_per_el)
    else:
        attn = sparse_attention_min_bytes(cfg, 0.0, 0.0, 0.0, live_tokens,
                                          bytes_per_el)
    return (always_read_bytes(cfg, bytes_per_el)
            + count(cfg, "L") * slots * 2 * state_row_bytes(cfg) + attn)
