"""Runtime flags (reference: platform/flags.cc ~40 gflags, exposed to Python
via FLAGS_* env vars parsed in __init__.py __bootstrap__ and
core.init_gflags, pybind.cc:1211).

Same contract: `FLAGS_check_nan_inf=1 python train.py` works, and
`set_flags({"FLAGS_check_nan_inf": True})` works programmatically.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_DEFAULTS: Dict[str, Any] = {
    # debugging (reference flags.cc:44)
    "FLAGS_check_nan_inf": False,
    # determinism (reference flags.cc:98 cudnn_deterministic)
    "FLAGS_deterministic": False,
    # executor behavior
    "FLAGS_use_program_cache": True,
    # profiler
    "FLAGS_profile_dir": "/tmp/paddle_tpu_profile",
    # attention kernel selection: "auto" (splash_attention for mask-free/
    # causal T>=1024 on TPU — tuned blocks beat XLA bf16-scores 2.2x at
    # T=4096, PROFILE.md round 4; XLA path otherwise), "splash" (force
    # splash on any eligible shape), "off" (always the XLA path)
    "FLAGS_flash_attention": "auto",
    # memory knobs recorded for parity (XLA owns allocation)
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_eager_delete_tensor_gb": 0.0,
    # async-PS communicator tuning (reference flags.cc:200-229 +
    # operators/distributed/communicator.cc:34-46)
    "FLAGS_communicator_max_merge_var_num": 20,
    "FLAGS_communicator_send_queue_size": 20,
    "FLAGS_communicator_independent_recv_thread": True,
    "FLAGS_communicator_min_send_grad_num_before_recv": 20,
    "FLAGS_communicator_thread_pool_size": 5,
    "FLAGS_communicator_send_wait_times": 5,
    "FLAGS_communicator_fake_rpc": False,
    "FLAGS_communicator_merge_sparse_grad": True,
}

_flags: Dict[str, Any] = {}


def _coerce(default, raw: str):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, float):
        return float(raw)
    if isinstance(default, int):
        return int(raw)
    return raw


def _bootstrap():
    for k, dv in _DEFAULTS.items():
        env = os.environ.get(k)
        _flags[k] = _coerce(dv, env) if env is not None else dv


_bootstrap()


def get_flags(keys=None) -> Dict[str, Any]:
    if keys is None:
        return dict(_flags)
    if isinstance(keys, str):
        keys = [keys]
    return {k: _flags[k] for k in keys}


def get_flag(key: str):
    return _flags[key]


def set_flags(d: Dict[str, Any]):
    for k, v in d.items():
        if k not in _flags:
            raise KeyError(f"unknown flag {k}; known: {sorted(_flags)}")
        _flags[k] = v
