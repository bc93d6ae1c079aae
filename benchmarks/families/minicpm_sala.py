"""MiniCPM-SALA as the benchmark drives it: `paddle_tpu.models
.minicpm_sala` parameters into the program's `DecodeEngine`, plus the
benchmark's own byte counts and plain reference.

What this family hands the harness beyond what `benchmarks/README.md` asks
of one ("What a family provides"; that file may not be edited by the PR
that adds a configuration, so it is said here):

- `init` WITHOUT a dtype returns no parameter set but a `LayerwiseParams`:
  the float32 set of the configuration (11.3 GB at 8 layers) does not fit a
  16 GB chip beside the reference's activations, so `top` (embedding, final
  norm, head: 2.4 GB) is on the device and `layer(i)` makes block i of the
  model's pattern (a sparse layer is the blocks `*E`, a lightning layer
  `ME`) from the seed when the reference asks for it, under the prefix
  `blk.`. `reference_gaps` walks its sequences through the blocks in turn.
- The slot count of the byte counts: the harness hands
  `decode_step_min_bytes` the `model` group and the live tokens only, but a
  decode step of this model reads and writes the lightning state of EVERY
  row it runs, and what a sparse layer reads of a row depends on the ROW's
  length, not on the pool's. The count comes from
  `harness/minicpm_sala_shapes.decode_step_min_bytes`'s default `slots=32`,
  which is `serve.decode_slots` of `configs/minicpm_sala.json`, the one
  configuration of this family; `tests/benchmarks/test_minicpm_sala_cell.py`
  holds the two equal.
- `kv_bytes_per_token` counts EVERY entry a token stores: K and V a sparse
  layer and the compressed key's share (512 B every 16 tokens), as the
  engine's `status()["kv"]["bytes_per_token_layer"]` does.
- The layer scopes `ssm` (the lightning layers' device ops, as Nemotron-H's
  recurrent layers'), `select` (the compressed keys' scores and the top-k)
  and `kc_write` (the compressed key a token or a slice of a prompt
  completes), the last two nested inside `attention`: the scopes a trace is
  reduced by are a tuple in `harness/program_trace.py` (`SCOPES`, and
  `COMPUTE` for `decode_compute_share`), a file a PR that adds a
  configuration may not edit, so `make_config` registers them there when
  the runner builds this family's model (before any trace is reduced, and
  in no run of another family): `register_scopes`. The reduction names an
  op by its INNERMOST scope, so the seconds of `attention` as a reader of
  this family means them are `attention` + `select` + `kc_write`
  (`ATTENTION_SCOPES`). PERF.md section 7 asks the next benchmark PR to
  put the three into the tuples and take the registration out.
- The switches of the reference (`dense_walk`, `sparse_rope`, `lin_rope`,
  `decay_one`, `bf16_state_layer`) are keys of the `model` group the
  reference alone reads: `make_config` drops them, so a control run may
  hand `reference_gaps` a faulty model and the program the right one.
"""

from __future__ import annotations

from typing import Dict

from ..harness import device, minicpm_sala_shapes
from ..reference import minicpm_sala_ref

# what a reader of this family sums for "the attention's seconds"
ATTENTION_SCOPES = ("attention", "select", "kc_write")
# keys of `model` that only the reference reads (its controls)
REFERENCE_SWITCHES = ("dense_walk", "sparse_rope", "lin_rope", "decay_one",
                      "bf16_state_layer")


def decode_scopes_of(rec: Dict):
    """(seconds by layer scope, all its seconds) of a traced serve run's
    decode program (of the programs with `decode` in their name, the one
    with most device time), or None: what this family's share readers
    read."""
    from ..harness import program_trace

    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes:
        return None
    steps = [p for name, p in scopes["programs"].items() if "decode" in name]
    if not steps:
        return None
    step = max(steps, key=lambda p: p["total_s"])
    return step["by_scope"], step["total_s"]


def register_scopes() -> None:
    """Make `ssm`, `select` and `kc_write` layer scopes of the trace
    reduction, and count the first two as the model's compute (the third
    moves a pool); idempotent."""
    from ..harness import program_trace

    for name, scopes in (("SCOPES", ("ssm", "select", "kc_write")),
                         ("COMPUTE", ("ssm", "select"))):
        have = getattr(program_trace, name)
        setattr(program_trace, name,
                have + tuple(s for s in scopes if s not in have))


def make_config(model: Dict):
    from paddle_tpu.models import minicpm_sala

    register_scopes()
    return minicpm_sala.MiniCPMSALAConfig(
        **{k: v for k, v in model.items() if k not in REFERENCE_SWITCHES})


class LayerwiseParams:
    """The float32 parameters `minicpm_sala.init(key(seed), cfg)` would
    hold, without holding them: `top` is on the device, `layer(i)` makes
    block i of the pattern (under `blk.`) from the seed when it is asked
    for."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import minicpm_sala

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: minicpm_sala.init_top(k, cfg))(
            self._key)
        # a block's index is static: it decides the block's kind
        self._layer = jax.jit(
            lambda k, i: minicpm_sala.init_layer(k, cfg, i),
            static_argnums=1)

    def layer(self, i: int):
        return self._layer(self._key, int(i))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import minicpm_sala

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each tensor as it makes it
    return device.init_on_device(
        lambda key, c: minicpm_sala.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return minicpm_sala_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return minicpm_sala_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return minicpm_sala_ref.stream_gaps(params.top, params.layer, model,
                                        prompts, streams, width)
