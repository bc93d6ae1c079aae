"""JoyAI-LLM-Flash as the benchmark drives it: `paddle_tpu.models.joyai`
parameters into the program's `DecodeEngine`, plus the benchmark's own byte
counts and plain reference. The float32 set of the configuration (22 GB at
5 layers) does not fit a 16 GB chip, so `init` without a dtype gives the
float32 parameters a layer at a time (`LayerwiseParams`) and the reference
walks its sequences through them in turn: one float32 expert layer is
4.96 GB beside 2.1 GB of embedding and head."""

from __future__ import annotations

from typing import Dict

from ..harness import device, joyai_shapes
from ..reference import joyai_ref


def make_config(model: Dict):
    from paddle_tpu.models import joyai

    return joyai.JoyaiConfig(**model)


class LayerwiseParams:
    """The float32 parameters `joyai.init(key(seed), cfg)` would hold,
    without holding them: `top` (embedding, final norm, head) is on the
    device, `layer(i)` makes layer i (counted over all layers, under the
    prefix `blk.`) from the seed when it is asked for: a dense layer below
    `cfg.dense_layers`, an expert layer from there on."""

    def __init__(self, cfg, seed: int):
        import jax

        from paddle_tpu.models import joyai

        self._cfg = cfg
        self._key = jax.random.key(seed % (2 ** 31))
        self.top = jax.jit(lambda k: joyai.init_top(k, cfg))(self._key)
        # a leading layer's index is static: it decides the layer's kind
        self._dense = jax.jit(lambda k, i: joyai.init_layer(k, cfg, i),
                              static_argnums=1)
        self._expert = jax.jit(lambda k, i: joyai.init_layer(k, cfg, i))

    def layer(self, i: int):
        import numpy as np

        if i < self._cfg.dense_layers:
            return self._dense(self._key, int(i))
        return self._expert(self._key, np.int32(i))


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import joyai

    if dtype is None:
        return LayerwiseParams(cfg, seed), {}
    # the model's own init casts each layer as it makes it
    return device.init_on_device(
        lambda key, c: joyai.init(key, c, dtype), cfg, seed)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return joyai_shapes.decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return joyai_shapes.kv_bytes_per_token(model)


def reference_gaps(params: LayerwiseParams, model: Dict, prompts, streams,
                   width: int):
    return joyai_ref.stream_gaps(params.top, params.layer, model, prompts,
                                 streams, width)
