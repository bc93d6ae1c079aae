"""Nemotron-H (Nemotron-3-Nano) as the benchmark drives it:
`paddle_tpu.models.nemotron_h` parameters into the program's
`DecodeEngine`, plus the benchmark's own byte counts and plain reference.

What this family does beyond what `benchmarks/README.md` asks of one:

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (24 GB at 9 blocks) does not fit a
  16 GB chip, so `top` (embedding, final norm, head: 2.82 GB) is on the
  device and `layer(i)` makes block i from the seed when the reference asks
  for it, under the prefix `blk.` and with the routed experts at their
  PUBLISHED width 1856 (the program lays them out padded to 1920 with
  zeros; the values are the same). One float32 expert block is 5.19 GB.
  `reference_gaps` walks its sequences through the blocks in turn.
- The slot count of the byte counts: the harness hands
  `decode_step_min_bytes` the `model` group and the live tokens only, but a
  decode step of this model also reads and writes the recurrent state of
  EVERY row it runs, which depends on the slots, not on the tokens. The
  count comes from `harness/nemotron_h_shapes.decode_step_min_bytes`'s
  default `slots=64`, which is `serve.decode_slots` of
  `configs/nemotron3_nano.json`, the one configuration of this family;
  `tests/benchmarks/test_nemotron_cell.py` holds the two equal.
- The layer scope `ssm`: the recurrent layers' device ops carry it, and the
  new per-layer metrics read the seconds under it, but the scopes a trace
  is reduced by are a tuple in `harness/program_trace.py` (`SCOPES`, and
  `COMPUTE` for `decode_compute_share`), a file a PR that adds a
  configuration may not edit. `make_config` therefore registers the scope
  there when the runner builds this family's model (before any trace is
  reduced, and in no run of another family): `register_scopes`. PERF.md
  section 7 asks the next benchmark PR to put `ssm` into the two tuples
  and take the registration out.
"""

from __future__ import annotations

from typing import Dict

from ..harness import device, nemotron_h_shapes
from ..reference import nemotron_h_ref


def register_scopes() -> None:
    """Make `ssm` a layer scope of the trace reduction and count it as the
    model's compute (idempotent)."""
    from ..harness import program_trace

    for name in ("SCOPES", "COMPUTE"):
        have = getattr(program_trace, name)
        if "ssm" not in have:
            setattr(program_trace, name, have + ("ssm",))


def make_config(model: Dict):
    from paddle_tpu.models import nemotron_h

    register_scopes()
    return nemotron_h.NemotronHConfig(**model)


class LayerwiseParams:
    """The float32 parameters `nemotron_h.init(key(seed), cfg)` would
    hold, without holding them: `top` is on the device, `layer(i)` makes
    block i of the pattern (under `blk.`, routed experts unpadded) from the
    seed when it is asked for."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import nemotron_h

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: nemotron_h.init_top(k, cfg))(self._key)
        # a block's index is static: it decides the block's kind
        self._layer = jax.jit(
            lambda k, i: nemotron_h.init_layer(k, cfg, i, pad=False),
            static_argnums=1)

    def layer(self, i: int):
        return self._layer(self._key, int(i))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import nemotron_h

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each tensor as it makes it
    return device.init_on_device(
        lambda key, c: nemotron_h.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return nemotron_h_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return nemotron_h_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return nemotron_h_ref.stream_gaps(params.top, params.layer, model,
                                      prompts, streams, width)
