"""LongCat (LongCat-Flash-Chat) as the benchmark drives it:
`paddle_tpu.models.longcat` parameters into the program's `DecodeEngine`,
plus the benchmark's own byte counts and plain reference.

What this family does beyond what `benchmarks/README.md` asks of one:

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (20.7 GB at 4 layers) does not fit a
  16 GB chip, so `top` (embedding, final norm, head: 0.81 GB) is on the
  device and `layer(i)` makes layer i (both sub-blocks, the router and the
  HELD experts, under the prefix `blk.`: 4.97 GB) from the seed when the
  reference asks for it. `reference_gaps` walks its sequences through the
  layers in turn.
- The layer scope `shortcut_experts`: the expert path's device ops carry
  it, a SIBLING of `mlp` (the dense MLPs), and the new per-layer metrics
  read the seconds under it, but the scopes a trace is reduced by are a
  tuple in `harness/program_trace.py` (`SCOPES`, and `COMPUTE` for
  `decode_compute_share`), a file a PR that adds a configuration may not
  edit. `make_config` therefore registers the scope there when the runner
  builds this family's model (before any trace is reduced, and in no run of
  another family), as `families/nemotron_h.py` registers `ssm`.
- `decode_step_min_bytes` counts the held experts a step reads at the
  EXPECTATION under a uniform router (`longcat_shapes.expected_experts_hit`:
  1 - (63/64)^128 of 16 a layer), as `families/olmoe.py` and
  `families/joyai.py` count theirs; what the window's step records counted
  (`experts_hit`) is `shortcut_expert_roofline`'s to read, from the records
  the harness hands a reader.
- The switches of the reference (`REFERENCE_SWITCHES`) are keys of the
  `model` group the reference alone reads: `make_config` drops them, so a
  control run may hand `reference_gaps` a faulty model and the program the
  right one.
"""

from __future__ import annotations

from typing import Dict

from ..harness import device, longcat_shapes
from ..reference import longcat_ref

SCOPE = "shortcut_experts"
# keys of `model` that only the reference reads (its controls)
REFERENCE_SWITCHES = ("held_term", "zero_term", "shortcut", "q_lora_scale",
                      "kv_lora_scale", "router_dtype", "score",
                      "bias_selects", "norm_topk_prob", "rope")


def is_longcat(rec: Dict) -> bool:
    """Whether a run's records are of this family: its `model` group alone
    has zero-compute experts."""
    return "zero_experts" in (rec.get("model") or {})


def pair_share(rec: Dict, counter: str):
    """Mean over the window's decode-step records of `counter` over the
    step's `pairs` (rows x top_k x layers), or None where the program's
    records carry no such counters."""
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    shares = [s[counter] / s["pairs"] for s in program["steps"]
              if s["kind"] == "decode" and counter in s and s.get("pairs")]
    return sum(shares) / len(shares) if shares else None


def register_scopes() -> None:
    """Make `shortcut_experts` a layer scope of the trace reduction and
    count it as the model's compute (idempotent)."""
    from ..harness import program_trace

    for name in ("SCOPES", "COMPUTE"):
        have = getattr(program_trace, name)
        if SCOPE not in have:
            setattr(program_trace, name, have + (SCOPE,))


def make_config(model: Dict):
    from paddle_tpu.models import longcat

    register_scopes()
    return longcat.LongcatConfig(
        **{k: v for k, v in model.items() if k not in REFERENCE_SWITCHES})


class LayerwiseParams:
    """The float32 parameters `longcat.init(key(seed), cfg)` would hold,
    without holding them: `top` is on the device, `layer(i)` makes layer i
    (under `blk.`) from the seed when it is asked for."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import longcat

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: longcat.init_top(k, cfg))(self._key)
        self._layer = jax.jit(lambda k, i: longcat.init_layer(k, cfg, i))

    def layer(self, i: int):
        import numpy as np

        return self._layer(self._key, np.int32(i))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import longcat

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each layer as it makes it
    return device.init_on_device(
        lambda key, c: longcat.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return longcat_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return longcat_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return longcat_ref.stream_gaps(params.top, params.layer, model, prompts,
                                   streams, width)
