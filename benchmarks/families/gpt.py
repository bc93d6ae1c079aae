"""GPT-2 as the benchmark drives it: `paddle_tpu.models.gpt` parameters into
the program's `DecodeEngine`, plus the benchmark's own byte count and plain
reference."""

from __future__ import annotations

from typing import Dict

from ..harness import device, shapes
from ..reference import gpt_ref


def make_config(model: Dict):
    from paddle_tpu.models import gpt

    return gpt.GPTConfig(**model)


def init(cfg, seed: int, dtype=None):
    from paddle_tpu.models import gpt

    return device.init_on_device(gpt.init, cfg, seed, dtype)


def decode_step_min_bytes(model: Dict, live_tokens: float) -> float:
    return shapes.gpt_decode_step_min_bytes(model, live_tokens)


def kv_bytes_per_token(model: Dict) -> int:
    return shapes.gpt_kv_bytes_per_token(model)


def reference_gaps(params, model: Dict, prompts, streams, width: int):
    return gpt_ref.stream_gaps(params, model, prompts, streams, width)
