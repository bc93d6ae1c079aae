"""Granite 4.0-H (granite-4.0-h-small) as the benchmark drives it:
`paddle_tpu.models.granite_hybrid` parameters into the program's
`DecodeEngine`, plus the benchmark's own byte counts and plain reference.

What this family does beyond what `benchmarks/README.md` asks of one:

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (19 GB at 10 layers) does not fit a
  16 GB chip, so `top` (the tied embedding and the final norm: 0.82 GB) is
  on the device and `layer(b)` makes BLOCK b from the seed when the
  reference asks for it, under the prefix `blk.`: block 2l is layer l's
  mixer (0.41 GB a Mamba-2 one), block 2l + 1 its experts (the router, the
  shared expert and the HELD routed experts: 1.44 GB). `reference_gaps`
  walks its sequences through the blocks in turn, as
  `families/nemotron_h.py` does.
- The slot count of the byte counts: a decode step reads and writes the
  recurrent state of EVERY row it runs, which depends on the slots and not
  on the live tokens the harness hands `decode_step_min_bytes`; the count
  comes from `harness/granite_hybrid_shapes.decode_step_min_bytes`'s default
  `slots=48`, which is `serve.decode_slots` of
  `configs/granite4_h_small.json`, the one configuration of this family
  (`tests/benchmarks/test_granite_cell.py` holds the two equal).
- The layer scope `ssm`: registered with the trace reduction when the runner
  builds this family's model, by `families/nemotron_h.register_scopes`
  (the scopes are a tuple in `harness/program_trace.py`, which a PR that
  adds a configuration may not edit).
- The `model` group carries Nemotron-H's key names where the meaning is the
  same (`ssm_heads`, `ssm_head_dim`, `ssm_groups`, `ssm_state`,
  `conv_kernel`, `pattern`: here a LAYER a character), so the readers that
  take their bytes from it (`ssm_share`, `ssm_update_roofline`,
  `gqa_attention_roofline`: `nemotron_h_shapes`) read this family's cell
  under their own stems; `held_swiglu_expert_roofline` and
  `held_expert_load_max_over_mean.granite4` are this family's own readers
  (`is_granite` tells its records from LongCat's, which also hold a share).
- The switches of the reference (`REFERENCE_SWITCHES`) are keys of the
  `model` group the reference alone reads: `make_config` drops them, so a
  control run may hand `reference_gaps` a faulty model and the program the
  right one. The four multipliers are the model's own keys: a control
  changes them in the reference's copy.
"""

from __future__ import annotations

from typing import Dict

from ..harness import device, granite_hybrid_shapes
from ..reference import granite_hybrid_ref
from .nemotron_h import register_scopes

# keys of `model` that only the reference reads (its controls)
REFERENCE_SWITCHES = ("rope", "norm_groups", "bc_per_head", "skip_D",
                      "dt_bias", "conv_bias", "shared_expert", "norm_topk",
                      "act", "held_term", "state_dtype", "stale_state",
                      "pad_tail", "prompt_len")


def is_granite(rec: Dict) -> bool:
    """Whether a run's records are of this family: its `model` group alone
    has the residual multiplier."""
    return "residual_multiplier" in (rec.get("model") or {})


def make_config(model: Dict):
    from paddle_tpu.models import granite_hybrid

    register_scopes()
    return granite_hybrid.GraniteHybridConfig(
        **{k: v for k, v in model.items() if k not in REFERENCE_SWITCHES})


class LayerwiseParams:
    """The float32 parameters `granite_hybrid.init(key(seed), cfg)` would
    hold, without holding them: `top` is on the device, `layer(b)` makes
    block b of `granite_hybrid.blocks(cfg.pattern)` (under `blk.`) from the
    seed when it is asked for."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import granite_hybrid

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(
            lambda k: granite_hybrid.init_top(k, cfg))(self._key)
        # a block's index is static: it decides the block's kind
        self._layer = jax.jit(
            lambda k, b: granite_hybrid.init_layer(k, cfg, b),
            static_argnums=1)

    def layer(self, b: int):
        return self._layer(self._key, int(b))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import granite_hybrid

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each tensor as it makes it
    return device.init_on_device(
        lambda key, c: granite_hybrid.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return granite_hybrid_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return granite_hybrid_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return granite_hybrid_ref.stream_gaps(params.top, params.layer, model,
                                          prompts, streams, width)
