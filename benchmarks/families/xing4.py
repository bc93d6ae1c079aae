"""Xing4.0-29B-A4B as the benchmark drives it: `paddle_tpu.models.xing4`
parameters into the program's `DecodeEngine`, plus the benchmark's own byte
counts and plain reference.

What this family hands the harness beyond what `benchmarks/README.md` asks
of one (that file may not be edited by the PR that adds a configuration, so
it is said here):

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (16.7 GB at 6 layers) does not fit a
  16 GB chip, so `top` (embedding, final norm, head: 3.8 GB) is on the
  device and `layer(i)` makes layer i (a dense layer below `dense_layers`,
  an expert layer from there on, each with the maps of its two sub-layers)
  from the seed when the reference asks for it, under the prefix `blk.`.
  `reference_gaps` takes ONE sequence at a time through the layers.
- The carried shape: a row of this model carries `hc_mult` x `hidden` = 14336
  values from block to block (`ServeModel.widen`), not `hidden`; nothing of
  the harness reads an activation, and the byte counts take the streams
  from `harness/xing4_shapes.py`.
- The slot count of the byte counts: the harness hands
  `decode_step_min_bytes` the `model` group and the live tokens only; the
  routed experts' expected count and the streams' bytes need the rows of a
  step, which is `xing4_shapes.decode_step_min_bytes`'s default `slots=32` =
  `serve.decode_slots` of `configs/xing4_29b_a4b.json`, the one
  configuration of this family (`tests/benchmarks/test_xing4_cell.py` holds
  the two equal).
- The layer scopes `mhc`, `mhc_map`, `mhc_pre` and `mhc_post` (the residual
  maps: the RMS, the product with Phi, the sigmoids and Sinkhorn; the mix
  into a sub-layer; the remix and the spread out of it; the last three
  nested in the first, which is a sibling of `attention` and `mlp`): the
  scopes a trace is reduced by are a tuple in `harness/program_trace.py`, a
  file this PR may not edit, so `make_config` registers them there when the
  runner builds this family's model (before any trace is reduced, and in no
  run of another family): `register_scopes`. The reduction names an op by
  its INNERMOST scope, so "the residual path's seconds" are the four
  together (`MHC_SCOPES`).
- `mhc_col_err` in every `decode.steps` record: the largest |column sum of
  H_res - 1| over the step's rows, sub-layers and layers, beside
  `experts_hit` and `expert_load_max`.
- The switches of the reference (`REFERENCE_SWITCHES`) are keys of the
  `model` group the reference alone reads: `make_config` drops them, so a
  control run may hand `reference_gaps` a faulty model and the program the
  right one.
"""

from __future__ import annotations

from typing import Dict

from ..harness import device, xing4_shapes
from ..reference import xing4_ref

# what a reader of this family sums for "the residual path's seconds"
MHC_SCOPES = ("mhc", "mhc_map", "mhc_pre", "mhc_post")
# keys of `model` that only the reference reads (its controls)
REFERENCE_SWITCHES = ("h_res_identity", "post_gain", "drop_stream", "yarn",
                      "mscale2", "sinkhorn_iters", "shared_expert")


def mhc_seconds(rec: Dict):
    """(the device seconds under the residual path's scopes, all the device
    seconds) of a traced serve run's decode program (of the programs with
    `decode` in their name, the one with most device time), or None where
    no op carries such a scope (another family's program, or the
    parent's)."""
    from ..harness import program_trace

    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes:
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    mhc_s = sum(step["by_scope"].get(s, 0.0) for s in MHC_SCOPES)
    return (mhc_s, step["total_s"]) if mhc_s > 0.0 else None


def register_scopes() -> None:
    """Make the residual path's scopes layer scopes of the trace reduction,
    and count them as the model's compute; idempotent."""
    from ..harness import program_trace

    for name in ("SCOPES", "COMPUTE"):
        have = getattr(program_trace, name)
        setattr(program_trace, name,
                have + tuple(s for s in MHC_SCOPES if s not in have))


def make_config(model: Dict):
    from paddle_tpu.models import xing4

    register_scopes()
    return xing4.Xing4Config(
        **{k: v for k, v in model.items() if k not in REFERENCE_SWITCHES})


class LayerwiseParams:
    """The float32 parameters `xing4.init(key(seed), cfg)` would hold,
    without holding them: `top` is on the device, `layer(i)` makes layer i
    (under `blk.`) from the seed when it is asked for."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import xing4

        self._cfg = cfg
        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: xing4.init_top(k, cfg))(self._key)
        # a leading layer's index is static: it decides the layer's kind
        self._dense = jax.jit(lambda k, i: xing4.init_layer(k, cfg, i),
                              static_argnums=1)
        self._expert = jax.jit(lambda k, i: xing4.init_layer(k, cfg, i))

    def layer(self, i: int):
        import numpy as np

        if i < self._cfg.dense_layers:
            return self._dense(self._key, int(i))
        return self._expert(self._key, np.int32(i))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import xing4

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each layer as it makes it
    return device.init_on_device(
        lambda key, c: xing4.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return xing4_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return xing4_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return xing4_ref.stream_gaps(params.top, params.layer, model, prompts,
                                 streams, width)
