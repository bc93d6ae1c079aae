"""Persistent content-addressed XLA compile cache.

Every process start — serving warmup of all buckets, gang restart
after a crash (distributed/launch.py --max_restarts), preemption
resume, a bench rerun — pays full XLA recompiles unless the compiled
executable survives the process. The reference framework's inference
layer ships serialized programs precisely so restart cost is I/O, not
compilation (paddle/fluid/inference/); this module is the analogous
layer for every `_JitDispatch` AOT compile: key the LOWERED module by
content, serialize the executable once, deserialize it forever after.

Key composition (sha256, hex):

    StableHLO text of the lowered module   — captures shapes, dtypes,
                                             shardings AND donation
                                             (`tf.aliasing_output`
                                             argument attributes)
    jax.__version__                        — executables are not stable
                                             across jax/jaxlib releases
    backend platform (cpu|tpu|gpu)
    device kind (e.g. "TPU v5 lite")       — a v4 executable must never
                                             load on a v5e
    XLA_FLAGS + default matmul precision   — compile options XLA reads
                                             outside the module text; a
                                             flag change must miss, not
                                             serve the old executable

The same fields are ALSO stored inside every entry and re-checked
on load, so a stale/collided/mixed-up entry falls back to a fresh
compile instead of executing the wrong computation.

TRUST MODEL: entries are pickles (the executable payload format is
pickle-based), and unpickling runs before any meta check can reject —
the cache directory must therefore be exactly as trusted as the model
files and checkpoints themselves (which this framework also
deserializes). The integrity machinery here protects against
corruption, version skew, and key collisions, NOT against an attacker
with write access to the directory; never point
PADDLE_TPU_COMPILE_CACHE at storage other principals can write to.

Entries are single files `<dir>/<key>.jex`: a pickle of a metadata dict
whose "payload" is the `jax.experimental.serialize_executable` blob.
Writes go through resilience/atomic.py (tmp + fsync + os.replace), so
concurrent writers of the same key land exactly one committed entry and
readers never observe a torn file; corrupt entries (truncated by a
pre-atomic-era crash, wrong version, unpicklable) are deleted and
counted, and the caller compiles fresh.

Env surface (documented in PROFILE.md §Compile-cache):

  PADDLE_TPU_COMPILE_CACHE             cache directory; unset/empty =
                                       disabled (the default)
  PADDLE_TPU_COMPILE_CACHE_MAX_BYTES   retention bound, default 1 GiB
  PADDLE_TPU_COMPILE_CACHE_MAX_ENTRIES retention bound, default 512

Retention sweeps oldest-mtime-first after each store; a load hit bumps
the entry's mtime, making the sweep LRU in practice.

JAX's OWN persistent compilation cache is a separate thing, placed once
per process by `place_jax_cache()` below: it serves every jit in the
process (this module's cache serves `_JitDispatch` only) and is what
lets a second chip run in the same checkout skip XLA.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..observability import telemetry as _telemetry

__all__ = ["enabled", "cache_dir", "fingerprint", "load", "store",
           "serialize_executable", "deserialize_executable",
           "entry_path", "sweep", "environment_meta", "place_jax_cache"]

_SUFFIX = ".jex"
_FORMAT = "paddle_tpu-compile-cache-v1"

_DEFAULT_MAX_BYTES = 1 << 30  # 1 GiB
_DEFAULT_MAX_ENTRIES = 512


def place_jax_cache() -> str:
    """Place JAX's persistent compilation cache for this process; call
    before the first compile (chip_smoke.py and serving/replica.py do,
    at the top). Where JAX_COMPILATION_CACHE_DIR is set, JAX's own
    handling of it is all there is and no other directory is set here. Where it is not, the cache goes to
    `<checkout>/.jax_cache` — a fixed path, because the path is part of
    the cache's key and a directory that moves never hits. Programs that
    compile in under a second (most of the decode phase grid) are kept
    too; JAX's minimum entry size is already 0. Returns the directory in
    use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def key_on_metadata() -> None:
    """Make JAX's persistent cache key on HLO metadata too. The layer
    scopes of models/ and parallel/train.py are metadata, which JAX
    leaves out of the key by default: an executable cached before a
    scope was added or renamed is then loaded again with the OLD labels
    ("executables loaded from the cache may have stale metadata, which
    may show up in profiles"), and a profile reduced by scope reads
    nothing or the wrong layer. `DecodeEngine` calls this before it
    compiles its phase grid, whose scopes the benchmark reads in every
    traced run. The price: a change that only moves source lines of a
    traced function recompiles the grid once, and a cache holds one set
    of entries per version of the source. `make_train_step` does NOT
    call it: one set of a BERT-base step's entries is 131 MiB, and a
    192 MiB cache that alternates between two versions then compiles
    every run (PERF.md, PR 24); whoever profiles a training step by
    scope sets the option for that run (`benchmarks/kinds/train.py`
    does, for a traced run's step)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def cache_dir() -> Optional[str]:
    d = os.environ.get("PADDLE_TPU_COMPILE_CACHE")
    # expand a literal "~" ourselves: docker ENV / env_file / systemd
    # set the var without a shell, and a cwd-relative "./~/..." dir
    # would silently stop hitting whenever the service's cwd moves
    return os.path.expanduser(d) if d else None


def enabled() -> bool:
    return cache_dir() is not None


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        return max(1, int(raw))
    except ValueError:
        return default


def environment_meta() -> Dict[str, str]:
    """The non-content key components — everything about THIS process
    that makes an executable loadable here and nowhere else. Includes
    the compile options XLA reads outside the module text (XLA_FLAGS,
    matmul precision): rerunning with e.g. fast-math disabled to chase
    a numerics bug must MISS, not silently serve the fast-math
    executable the flags no longer describe (jax's own persistent
    cache keys compile options for the same reason)."""
    try:
        dev = jax.devices()[0]
        backend, kind = dev.platform, dev.device_kind
    except Exception:
        backend, kind = "unknown", "unknown"
    try:
        precision = str(jax.config.jax_default_matmul_precision
                        or "default")
    except Exception:
        precision = "default"
    return {"jax_version": jax.__version__, "backend": backend,
            "device_kind": kind,
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "matmul_precision": precision}


def fingerprint(lowered, extra: Optional[str] = None) -> Optional[str]:
    """Content address of a `jax.stages.Lowered`: sha256 over the
    StableHLO module text + the environment meta + `extra` caller key
    material. None when the module text is unavailable (exotic
    lowerings) — caller compiles fresh.

    `extra` carries per-dispatch key components that are neither module
    content nor process environment — today the PRECISION POLICY name
    (_JitDispatch.cache_fingerprint): two policies usually lower to
    different StableHLO anyway, but the policy is kept as explicit key
    material so a policy flip is GUARANTEED to miss even for a program
    whose lowered text happens to be width-invariant."""
    try:
        text = lowered.as_text()
    except Exception:
        return None
    h = hashlib.sha256()
    h.update(text.encode())
    for k, v in sorted(environment_meta().items()):
        h.update(b"\0")
        h.update(f"{k}={v}".encode())
    if extra:
        h.update(b"\0extra=")
        h.update(str(extra).encode())
    return h.hexdigest()


def entry_path(key: str, d: Optional[str] = None) -> str:
    return os.path.join(d or cache_dir() or "", key + _SUFFIX)


# ---------------------------------------------------------------------------
# Executable (de)serialization — shared with the serving warmstart
# artifact (serving/engine.py), which stores these blobs per bucket.
# ---------------------------------------------------------------------------


def serialize_executable(compiled) -> bytes:
    """One opaque blob for a `jax.stages.Compiled`: the pjrt payload,
    the in/out pytree defs it needs to be callable again, and the ids of
    the devices it executes on. Raises when the backend doesn't support
    serialization (caller falls back to leaving the plain compile in
    place)."""
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree = _se.serialize(compiled)
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_executable(blob: bytes):
    """Inverse of serialize_executable: a loaded, callable executable
    bound to the same devices (by id) it was compiled for. The devices
    are passed explicitly: left to its default, deserialize_and_load
    loads for ALL of the backend's devices, and a one-device executable
    on a many-device host then refuses its arguments ("expected N
    shards")."""
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


# ---------------------------------------------------------------------------
# Load / store
# ---------------------------------------------------------------------------


def _drop_entry(path: str) -> bool:
    try:
        os.unlink(path)
        return True
    except OSError:
        return False


def load(key: str, kind: str):
    """Deserialized executable for `key`, or None on miss. A corrupt or
    environment-mismatched entry is deleted, counted, and reported as a
    miss — the caller's fresh compile then overwrites it. Never raises:
    any cache failure degrades to a compile, not an error."""
    d = cache_dir()
    if d is None or not key:
        return None
    path = entry_path(key, d)
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        _telemetry.record_compile_cache(kind, "miss", key=key)
        return None
    try:
        entry = pickle.loads(raw)
        if not isinstance(entry, dict) or entry.get("format") != _FORMAT:
            raise ValueError("not a compile-cache entry")
        if entry.get("key") != key:
            # entry bytes under the wrong filename (copied/renamed
            # cache dir): env meta matches every entry on this host,
            # so without this check a mixed-up file would serve the
            # WRONG program's executable
            raise ValueError(f"key mismatch: entry says "
                             f"{str(entry.get('key'))[:16]}…")
        env = environment_meta()
        stored = {k: entry.get(k) for k in env}
        if stored != env:
            raise ValueError(f"environment mismatch: entry {stored} "
                             f"vs process {env}")
        exe = deserialize_executable(entry["payload"])
    except Exception as e:
        # truncated pickle, version/device mismatch, pjrt refusal —
        # all the same outcome: drop the entry, compile fresh
        _drop_entry(path)
        _telemetry.record_compile_cache(kind, "corrupt", key=key,
                                        error=str(e)[:200])
        return None
    try:
        os.utime(path)  # LRU bump for the retention sweep
    except OSError:
        pass
    _telemetry.record_compile_cache(
        kind, "hit", nbytes=len(raw), key=key,
        seconds=time.perf_counter() - t0)
    return exe


def store(key: str, compiled, kind: str) -> bool:
    """Serialize + atomically publish `compiled` under `key`, then
    sweep retention. Returns whether a commit happened. Never raises:
    a backend that can't serialize, or a full/read-only disk, costs
    only the caching — the compile already succeeded."""
    d = cache_dir()
    if d is None or not key:
        return False
    try:
        blob = serialize_executable(compiled)
        entry = dict(environment_meta(), format=_FORMAT, key=key,
                     kind=kind, created_at=time.time(), payload=blob)
        raw = pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL)
        from ..resilience.atomic import write_bytes

        write_bytes(entry_path(key, d), raw)
    except Exception as e:
        _telemetry.record_compile_cache(kind, "store_error", key=key,
                                        error=str(e)[:200])
        return False
    _telemetry.record_compile_cache(kind, "store", nbytes=len(raw),
                                    key=key)
    sweep(d)
    return True


def sweep(d: Optional[str] = None) -> int:
    """Enforce the byte/entry retention bounds, evicting oldest-mtime
    first. Returns how many entries were evicted. Evictions are
    recorded under kind="cache": attributing them to whichever kind's
    store happened to trigger the sweep would misdirect an operator
    reading the per-kind table (the evicted entries usually belong to
    OTHER kinds), and reading each entry back just to label its drop
    would make every store O(cache)."""
    d = d or cache_dir()
    if d is None:
        return 0
    max_bytes = _env_int("PADDLE_TPU_COMPILE_CACHE_MAX_BYTES",
                         _DEFAULT_MAX_BYTES)
    max_entries = _env_int("PADDLE_TPU_COMPILE_CACHE_MAX_ENTRIES",
                           _DEFAULT_MAX_ENTRIES)
    entries: List[Tuple[float, int, str]] = []
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    for name in names:
        if not name.endswith(_SUFFIX):
            continue
        path = os.path.join(d, name)
        try:
            st = os.stat(path)
        except OSError:
            continue  # concurrently evicted
        entries.append((st.st_mtime, st.st_size, path))
    entries.sort()  # oldest first
    total = sum(s for _, s, _ in entries)
    n_left = len(entries)
    evicted = 0
    while entries and (total > max_bytes or n_left > max_entries):
        _, size, path = entries.pop(0)
        if not _drop_entry(path):
            continue  # undeletable (foreign owner): try the next-oldest
        total -= size
        n_left -= 1
        evicted += 1
        _telemetry.record_compile_cache("cache", "evict", nbytes=size)
    return evicted
