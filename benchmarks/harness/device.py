"""The chip under the benchmark: it must be there, it must be in the table
of peaks, and nothing may compile inside a measured window."""

from __future__ import annotations

import collections
import json
import os
from typing import Dict

from .manifest import BENCH_DIR, ROOT

COUNTS: collections.Counter = collections.Counter()
_COMPILE_REQUEST = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def place_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else `<checkout>/.jax_cache` (a fixed path: the path is part of
    the key). Every program is kept, however quickly it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def listen() -> None:
    """Count compile requests (a persistent-cache hit is still a request:
    'zero compiles in the window' means zero NEW programs)."""
    import jax

    def on_duration(event, secs, **kw):
        if event == _COMPILE_REQUEST:
            COUNTS["compile_requests"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def load_peaks(kind: str, path: str = os.path.join(BENCH_DIR, "peaks.json")
               ) -> Dict:
    with open(path) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(
            f"device_kind {kind!r} is not in {path}: add its published "
            "peaks with their source; there is no default")
    return table[kind]


def require(chips: int, allow_cpu: bool = False) -> Dict:
    """The device dict of the result line, or NoAccelerator. `allow_cpu` is
    for the tiny rehearsals under tests/benchmarks only; run.py never sets
    it, so a run off the chip fails instead of falling back."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu" and not allow_cpu:
        raise NoAccelerator(f"JAX found no accelerator: {devs[:2]}")
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips and JAX has {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def start(chips: int, allow_cpu: bool = False):
    """What every runner does first: place the cache, count compiles, find
    the chips. Returns (device dict, the kind's row of peaks or None on the
    tests' CPU)."""
    place_cache()
    listen()
    dev = require(chips, allow_cpu)
    return dev, (None if dev["platform"] == "cpu"
                 else load_peaks(dev["kind"]))


def init_on_device(init, cfg, seed: int, dtype=None):
    """(params, axes) of the program's `init(key, cfg)` as ONE jitted
    program, made on the device from the seed (called eagerly, init costs a
    compile per distinct parameter shape). With `dtype`, every floating
    parameter is cast to it INSIDE that program: the values are the float32
    ones rounded once, and the float32 set is never whole on the device."""
    import jax
    import jax.numpy as jnp

    axes: Dict = {}

    def _init(key):
        params, a = init(key, cfg)
        axes.update(a)  # static: filled once, while tracing
        if dtype is not None:
            params = jax.tree_util.tree_map(
                lambda v: v.astype(dtype)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, params)
        return params

    return jax.jit(_init)(jax.random.key(seed % (2 ** 31))), axes


def resident_bytes(devices) -> int:
    """Bytes of live arrays on the fullest of `devices`, from shapes and
    shardings alone: asking an array for its shards caches them on it in a
    cycle that the collector does not free, and would keep every array
    counted here alive for good. A single-device buffer counts once
    however many array objects share it (a compiled call hands its
    arguments on under new array objects)."""
    import math

    import jax

    per = collections.Counter()
    ids = {d.id for d in devices}
    seen = set()
    for arr in jax.live_arrays():
        if arr.is_deleted():
            continue
        on = [d.id for d in arr.sharding.device_set if d.id in ids]
        if len(arr.sharding.device_set) == 1:
            buf = arr.unsafe_buffer_pointer()
            if buf in seen:
                continue
            seen.add(buf)
        nbytes = math.prod(arr.sharding.shard_shape(arr.shape)) \
            * arr.dtype.itemsize
        for i in on:
            per[i] += nbytes
    return max(per.values()) if per else 0


def runtime_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def planned_bytes(compiled) -> Dict:
    """The compiler's plan for one program: arguments + outputs that do not
    alias them + temporaries. This runtime's `peak_bytes_in_use` leaves
    temporaries out (PERF.md finding, PR 21), so the plan is what is read."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {k: int(getattr(ma, k + "_size_in_bytes", 0) or 0)
           for k in ("argument", "output", "temp", "alias",
                     "generated_code")}
    out["total"] = (out["argument"] + out["output"] - out["alias"]
                    + out["temp"] + out["generated_code"])
    return out
