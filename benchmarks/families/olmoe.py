"""OLMoE as the benchmark drives it: `paddle_tpu.models.olmoe` parameters
into the program's `DecodeEngine`, plus the benchmark's own byte counts and
plain reference. The float32 set of the configuration (14 GB at 8 layers)
does not fit a 16 GB chip beside anything, so `init` without a dtype gives
the float32 parameters a layer at a time (`LayerwiseParams`) and the
reference walks its sequences through them in turn."""

from __future__ import annotations

from typing import Dict

from ..harness import device, olmoe_shapes
from ..reference import olmoe_ref


def make_config(model: Dict):
    from paddle_tpu.models import olmoe

    return olmoe.OlmoeConfig(**model)


class LayerwiseParams:
    """The float32 parameters `olmoe.init(key(seed), cfg)` would hold,
    without holding them: `top` (embedding, final norm, head) is on the
    device, `layer(i)` makes layer i from the seed when it is asked for
    (the served set is these values rounded once, but for the last bit of
    a few: `olmoe.init_layer`)."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import olmoe

        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: olmoe.init_top(k, cfg))(self._key)
        self._layer = jax.jit(lambda k, i: olmoe.init_layer(k, cfg, i))

    def layer(self, i: int):
        return self._layer(self._key, i)


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import olmoe

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each layer as it makes it
    return device.init_on_device(
        lambda key, c: olmoe.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return olmoe_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return olmoe_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return olmoe_ref.stream_gaps(params.top, params.layer, model, prompts,
                                 streams, width)
