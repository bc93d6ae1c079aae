"""Jamba (AI21-Jamba2-3B) as the benchmark drives it:
`paddle_tpu.models.jamba` parameters into the program's `DecodeEngine`, plus
the benchmark's own byte counts and plain reference.

What this family does beyond what `benchmarks/README.md` asks of one:

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (12.1 GB at 28 layers) does not fit
  a 16 GB chip beside the reference's activations, so `top` (the embedding,
  which is the head, and the final norm: 0.67 GB) is on the device and
  `layer(i)` makes block i of the model's pattern (a Mamba layer is the
  blocks `ME`, an attention layer `*E`: 56 blocks) from the seed when the
  reference asks for it, under the prefix `blk.`. `reference_gaps` walks
  its sequences through the blocks in turn.
- The slot count of the byte counts: the harness hands
  `decode_step_min_bytes` the `model` group and the live tokens only, but a
  decode step of this model reads and writes the recurrent state of EVERY
  row it runs, which depends on the slots, not on the tokens. The count
  comes from `harness/jamba_shapes.decode_step_min_bytes`'s default
  `slots=128`, which is `serve.decode_slots` of `configs/jamba2_3b.json`,
  the one configuration of this family;
  `tests/benchmarks/test_jamba_cell.py` holds the two equal.
- The layer scope `ssm`: the recurrent layers' device ops carry it, and the
  new per-layer metrics read the seconds under it, but the scopes a trace
  is reduced by are a tuple in `harness/program_trace.py` (`SCOPES`, and
  `COMPUTE` for `decode_compute_share`), a file a PR that adds a
  configuration may not edit. `make_config` therefore registers the scope
  there when the runner builds this family's model (before any trace is
  reduced, and in no run of another family): `families/nemotron_h.py`'s
  `register_scopes`, as `families/minicpm_sala.py` registers its own. PERF.md
  section 7 asks the next benchmark PR to put `ssm` into the two tuples and
  take the three registrations out.
- The switches of the reference (`REFERENCE_SWITCHES`) are keys of the
  `model` group the reference alone reads: `make_config` drops them, so a
  control run may hand `reference_gaps` a faulty model and the program the
  right one.
"""

from __future__ import annotations

from typing import Dict

from ..harness import device, jamba_shapes
from ..reference import jamba_ref
# `ssm` as a layer scope of the trace reduction and as the model's compute:
# the same registration, for the same reason
from .nemotron_h import register_scopes

# keys of `model` that only the reference reads (its controls)
REFERENCE_SWITCHES = ("state_dtype", "scalar_decay", "dt_norm", "b_norm",
                      "c_norm", "conv_bias", "dt_bias", "skip_D", "rope",
                      "learned_pos", "pad_tail", "pad_conv", "prompt_len")


def is_jamba(rec: Dict) -> bool:
    """Whether a run's records are of this family: its `model` group alone
    has the low-rank dt beside a state size."""
    model = rec.get("model") or {}
    return "dt_rank" in model and "ssm_state" in model


def make_config(model: Dict):
    from paddle_tpu.models import jamba

    register_scopes()
    return jamba.JambaConfig(
        **{k: v for k, v in model.items() if k not in REFERENCE_SWITCHES})


class LayerwiseParams:
    """The float32 parameters `jamba.init(key(seed), cfg)` would hold,
    without holding them: `top` is on the device, `layer(i)` makes block i
    of the pattern (under `blk.`) from the seed when it is asked for."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import jamba

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: jamba.init_top(k, cfg))(self._key)
        # a block's index is static: it decides the block's kind
        self._layer = jax.jit(
            lambda k, i: jamba.init_layer(k, cfg, i), static_argnums=1)

    def layer(self, i: int):
        return self._layer(self._key, int(i))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import jamba

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each tensor as it makes it
    return device.init_on_device(
        lambda key, c: jamba.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return jamba_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return jamba_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return jamba_ref.stream_gaps(params.top, params.layer, model, prompts,
                                 streams, width)
