"""Trinity (`model_type` `afmoe`, Trinity-Mini) as the benchmark drives it:
`paddle_tpu.models.afmoe` parameters into the program's `DecodeEngine`, plus
the benchmark's own byte counts and plain reference.

What this family does beyond what `benchmarks/README.md` asks of one:

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (12.6 GB at 8 layers) does not fit a
  16 GB chip beside a 36k-token forward pass, so `top` (embedding, head and
  final norm: 1.64 GB) is on the device and `layer(b)` makes BLOCK b from
  the seed when the reference asks for it, under the prefix `blk.`: block 2l
  is layer l's attention (0.11 GB), block 2l + 1 its MLP (a dense one 0.15
  GB; the router, the shared expert and the HELD routed experts 1.64 GB).
  `reference_gaps` walks its sequences through the blocks in turn, as
  `families/granite_hybrid.py` does. A control that adds the ABSENT
  experts' term (`held_term` "all") gets every block with all `n_experts`.
- The slot count of the byte counts: a decode step reads a WINDOW a slot in
  the sliding layers whatever the live tokens are, which the harness does
  not hand `decode_step_min_bytes`; the count comes from
  `harness/afmoe_shapes.decode_step_min_bytes`'s default `slots=32`, which
  is `serve.decode_slots` of `configs/trinity_mini.json`, the one
  configuration of this family (`tests/benchmarks/test_trinity_cell.py`
  holds the two equal).
- The layer scope `window_attention` (the sliding layers' reads; the full
  layers' stay under `attention`): registered with the trace reduction when
  the runner builds this family's model (`register_scopes`), since the
  scopes are a tuple in `harness/program_trace.py`, which a PR that adds a
  configuration may not edit.
- `kv_bytes_per_token` is the GLOBAL kind's (the harness multiplies it by
  `kv_pool_tokens`, which is `num_blocks`' kind); the engine sizes the
  window kind's pools itself and `status()["kv"]["pool_bytes"]`, which the
  runner records as `kv_pool_bytes`, counts both.
- The per-layer readers of this family's own (`*.trinity`) take their bytes
  from `harness/afmoe_shapes.py` and tell its records by `is_afmoe`.
- The switches of the reference (`REFERENCE_SWITCHES`) are keys of the
  `model` group the reference alone reads: `make_config` drops them, so a
  control run may hand `reference_gaps` a faulty model and the program the
  right one. `window`, `route_scale` and `mup_enabled` are the model's own
  keys: a control changes them in the reference's copy.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from ..harness import afmoe_shapes, device
from ..reference import afmoe_ref

SCOPE = "window_attention"

# keys of `model` that only the reference reads (its controls)
REFERENCE_SWITCHES = ("window_all", "rope_full", "rope_sliding",
                      "output_gate", "qk_norm", "post_norms", "norm_topk",
                      "bias_selects", "shared_expert", "held_term",
                      "ring_short", "pad_tail", "stale_ring", "prompt_len",
                      "block", "q_block")


def is_afmoe(rec: Dict) -> bool:
    """Whether a run's records are of this family: its `model` group alone
    has a window beside leading dense layers."""
    model = rec.get("model") or {}
    return "window" in model and "dense_layers" in model


def register_scopes() -> None:
    """Make `window_attention` a layer scope of the trace reduction and
    count it as the model's compute (idempotent)."""
    from ..harness import program_trace

    for name in ("SCOPES", "COMPUTE"):
        have = getattr(program_trace, name)
        if SCOPE not in have:
            setattr(program_trace, name, have + (SCOPE,))


def make_config(model: Dict):
    from paddle_tpu.models import afmoe

    register_scopes()
    return afmoe.AfmoeConfig(
        **{k: v for k, v in model.items() if k not in REFERENCE_SWITCHES})


class LayerwiseParams:
    """The float32 parameters `afmoe.init(key(seed), cfg)` would hold,
    without holding them: `top` is on the device, `layer(b)` makes block b
    of `afmoe.blocks(cfg.pattern)` (under `blk.`) from the seed when it is
    asked for; `whole` the same with EVERY routed expert (a control's)."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import afmoe

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: afmoe.init_top(k, cfg))(self._key)
        uncut = dataclasses.replace(cfg, held=None)
        # a block's index is static: it decides the block's stack
        self._layer = jax.jit(
            lambda k, b: afmoe.init_layer(k, cfg, b), static_argnums=1)
        self._whole = jax.jit(
            lambda k, b: afmoe.init_layer(k, uncut, b), static_argnums=1)

    def layer(self, b: int):
        return self._layer(self._key, int(b))

    def whole(self, b: int):
        return self._whole(self._key, int(b))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import afmoe

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each tensor as it makes it
    return device.init_on_device(
        lambda key, c: afmoe.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return afmoe_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return afmoe_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    layer = params.whole if model.get("held_term") == "all" else params.layer
    return afmoe_ref.stream_gaps(params.top, layer, model, prompts, streams,
                                 width)
