"""Inference predictor — deployment API.

Reference: paddle/fluid/inference/ — `PaddlePredictor`/`AnalysisPredictor`
(api/paddle_api.h:204, api/analysis_predictor.h:47): load a saved inference
model, run an analysis/optimization pipeline, expose Run()/ZeroCopyRun with
a config object (AnalysisConfig).

TPU-native: the "analysis pipeline" is XLA — the loaded program lowers to
one jit-compiled (optionally AOT-compiled) computation per input signature.
Zero-copy semantics come from device-resident params + donated inputs.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import io
from .core import lowering
from .core import precision as _precision
from .core.executor import Executor, Scope, _JitDispatch, scope_guard
from .core.ir import normalize_dtype
from .core.places import (CPUPlace, Place, TPUPlace, default_place,
                          is_compiled_with_tpu)


class AnalysisConfig:
    """reference: inference/api/analysis_config.cc — knobs subset that is
    meaningful on TPU; the rest are accepted and recorded for parity."""

    def __init__(self, model_dir: Optional[str] = None):
        self.model_dir = model_dir
        # None = the chip if this process has one, else the host (as
        # default_place()); enable_use_gpu()/disable_gpu() make it a
        # request that TPUPlace/CPUPlace then hold the process to
        self._use_tpu: Optional[bool] = None
        self._device_id = 0
        self._memory_optim = True       # XLA buffer assignment
        self._ir_optim = True           # XLA fusion
        self._enable_profile = False
        self._aot = False               # ahead-of-time compile at load
        self._native_engine = False     # C++ interpreter (capi) backend
        self._bucketing = None          # serving.bucketing.BucketPolicy
        self._precision = None          # core.precision policy name

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_tpu = True  # accelerator = TPU in this framework
        self._device_id = device_id

    def disable_gpu(self):
        self._use_tpu = False

    def switch_ir_optim(self, x=True):
        self._ir_optim = x

    def enable_memory_optim(self):
        self._memory_optim = True

    def enable_profile(self):
        self._enable_profile = True

    def enable_aot(self):
        self._aot = True

    def enable_bucketing(self, max_batch: int = 64, buckets=None):
        """Round every Run() batch up to the nearest configured bucket
        (powers of two up to `max_batch` by default, or an explicit
        `buckets` sequence), padding feeds and slicing outputs back to
        the true batch — so bs=1..64 traffic hits at most log2(64)+1
        compiled signatures instead of up to 64. Batches larger than
        the biggest bucket fall back to exact-shape compilation. See
        SERVING.md §Bucket policy."""
        from .serving.bucketing import BucketPolicy

        self._bucketing = BucketPolicy(max_batch=max_batch,
                                       buckets=buckets)

    def set_precision(self, name: Optional[str]):
        """Serve under a named precision policy (core/precision.py:
        "f32" | "bf16" | "mixed_bf16"): floating feeds normalize to the
        policy's compute dtype and the loaded program lowers under its
        autocast. Resolution order: this config > the loaded program's
        precision attr > PADDLE_TPU_PRECISION > f32. Ignored by the
        native (C++) engine, which is f32-only."""
        if name is not None:
            _precision.get_policy(name)  # fail fast on typos
        self._precision = name

    def enable_native_engine(self):
        """Serve through the C++ interpreter (native/src/predictor.cc) —
        the reference's analogous switch is picking the Native vs Analysis
        predictor (api/api_impl.h); here it swaps the XLA engine for the
        dependency-free CPU one."""
        self._native_engine = True


class PaddleTensor:
    """reference: api/paddle_api.h PaddleTensor — named ndarray."""

    def __init__(self, data, name: str = ""):
        self.name = name
        self.data = np.asarray(data)

    @property
    def shape(self):
        return self.data.shape


class Predictor:
    """reference: AnalysisPredictor. Loads the model once; each distinct
    input signature compiles once and is cached (the reference caches one
    engine per optimized graph)."""

    def __init__(self, config: AnalysisConfig):
        self.config = config
        if config._native_engine:
            from .capi import NativePredictor

            self._native = NativePredictor(config.model_dir)
            self._feed_names = self._native.input_names
            self._fetch_names = self._native.output_names
            # declared feed dtypes: the native engine gets the same
            # feed-dtype normalization the XLA path performs
            import json

            with open(os.path.join(config.model_dir, "__model__")) as f:
                payload = json.load(f)
            feed_set = set(self._feed_names)
            # first match across blocks wins (same rule as the XLA path) —
            # a sub-block local sharing a feed name must not shadow it
            self._feed_dtypes = {}
            for b in payload["program"]["blocks"]:
                for v in b["vars"]:
                    if v["name"] in feed_set and \
                            v["name"] not in self._feed_dtypes:
                        self._feed_dtypes[v["name"]] = v.get("dtype",
                                                             "float32")
            return
        self._native = None
        use_tpu = is_compiled_with_tpu() if config._use_tpu is None \
            else config._use_tpu
        place = TPUPlace(config._device_id) if use_tpu else CPUPlace()
        self._exe = Executor(place)
        self._scope = Scope()
        with scope_guard(self._scope):
            (self._program, self._feed_names,
             self._fetch_vars) = io.load_inference_model(
                config.model_dir, self._exe)
        self._fetch_names = [v if isinstance(v, str) else v.name
                             for v in self._fetch_vars]
        self._program._is_test = True
        # one policy per Predictor, resolved at load: config >
        # program attr (a model saved under a policy keeps it) > env
        self._policy = _precision.resolve(self._program,
                                          explicit=config._precision)
        self._cache: Dict = {}
        # which fetches carry the batch dim (declared leading dim is
        # dynamic): bucketing must never slice an output whose fixed
        # leading dim merely coincides with the bucket size. None =
        # shape undeclared → fall back to the runtime-shape heuristic.
        self._fetch_batched: Dict[str, Optional[bool]] = {}
        for name in self._fetch_names:
            self._fetch_batched[name] = self._var_batched(name)
        # feeds get the symmetric treatment: a feed whose declared
        # leading dim is fixed (lookup tables, masks) must be neither
        # counted toward the batch size nor padded
        self._feed_batched: Dict[str, Optional[bool]] = {
            name: self._var_batched(name) for name in self._feed_names}

    def _var_batched(self, name: str) -> Optional[bool]:
        """Does `name`'s declared leading dim carry the batch (-1/0 =
        dynamic)? None when the shape is undeclared."""
        var = self._find_var(name)
        shape = var.shape if var is not None else None
        if shape is None:
            return None
        return bool(shape) and shape[0] in (-1, 0)

    def _find_var(self, name: str):
        """First match across blocks (a sub-block local must not shadow
        the outer var — same rule the native path applies to feeds)."""
        for b in self._program.desc.blocks:
            if name in b.vars:
                return b.vars[name]
        return None

    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def _compiled(self, sig, warm: Optional[bool] = None):
        step = self._cache.get(sig)
        if step is None:
            desc = self._program.desc
            feed_names = tuple(n for n, _, _ in sig)
            policy = self._policy

            def fwd(feeds, state):
                env = dict(state)
                env.update(feeds)
                with _precision.autocast(policy):
                    lowering.lower_block(desc, 0, env, rng_key=None,
                                         is_test=True)
                return [env[n] for n in self._fetch_names]

            state = {}
            for b in desc.blocks:
                for name, v in b.vars.items():
                    if v.persistable:
                        val = self._scope.find_var(name)
                        if val is not None:
                            arr = jnp.asarray(val)
                            if policy.cast_state:
                                # pure low-precision serving: params are
                                # cast ONCE here, not per request
                                arr = _precision.cast_floating(
                                    arr, policy.compute_dtype)
                            state[name] = arr
            # _JitDispatch: compiles land in paddle_tpu_compile_seconds
            # {kind="infer"} and the `compile` event log, so a serving
            # deployment can assert its bucket set stays closed
            jitted = _JitDispatch(jax.jit(fwd), "infer", meta={
                "signature": ",".join(f"{n}:{list(s)}" for n, s, _ in sig)},
                policy=policy.name)
            # warm=False (adopt_warm) builds the slot for an executable
            # that already exists — warming would compile the very thing
            # the warmstart artifact exists to skip
            if self.config._aot if warm is None else warm:
                shapes = {n: jax.ShapeDtypeStruct(s, np.dtype(d))
                          for n, s, d in sig}
                jitted.warm(shapes, state)
            step = (jitted, state)
            self._cache[sig] = step
        return step

    def _feed_sig(self, batch_size: int):
        """Signature tuple for the declared feed shapes at `batch_size`
        (leading dynamic dim replaced; any other dynamic dim is an
        error — such a model must be warmed by running a real batch)."""
        entries = []
        for name in self._feed_names:
            var = self._find_var(name)
            if var is None or var.shape is None:
                raise ValueError(f"feed '{name}' has no declared shape; "
                                 "cannot warm ahead of traffic")
            shape = [int(d) for d in var.shape]
            if shape and shape[0] in (-1, 0):
                shape[0] = int(batch_size)
            if any(d < 1 for d in shape):
                raise ValueError(
                    f"feed '{name}' has non-batch dynamic dims "
                    f"{tuple(var.shape)}; warm it with a real batch")
            dtype = self._policy.feed_dtype(
                np.dtype(normalize_dtype(var.dtype)))
            entries.append((name, tuple(shape), str(dtype)))
        return tuple(sorted(entries))

    def warm(self, batch_size: int) -> bool:
        """AOT-compile the signature for `batch_size` without executing
        — a bucketed serving deployment warms every configured bucket at
        startup so no live request pays a compile. No-op on the native
        engine (no XLA). Returns whether an AOT executable is ready."""
        if self._native is not None:
            return False
        sig = self._feed_sig(batch_size)
        jitted, state = self._compiled(sig)
        shapes = {n: jax.ShapeDtypeStruct(s, np.dtype(d))
                  for n, s, d in sig}
        return jitted.warm(shapes, state)

    # -- warmstart (serialized-executable) export/import ---------------

    def serialize_warm(self) -> Dict[Tuple, Dict]:
        """Serialized executable per cached signature whose AOT compile
        is ready — the payload of a serving warmstart artifact
        (SERVING.md §Warmstart). Each entry carries the signature's
        lowering FINGERPRINT (compile_cache.fingerprint over the
        StableHLO this process's paddle_tpu emits, plus the environment
        meta), re-checked at adoption: an artifact baked before a
        lowering change must fall back to compiling, never serve the
        old computation. Signatures a backend refuses to serialize are
        skipped, not fatal: the artifact then simply covers fewer
        buckets and boot compiles the rest."""
        from .core import compile_cache

        out: Dict[Tuple, Dict] = {}
        for sig, (jitted, state) in self._cache.items():
            exe = getattr(jitted, "_aot", None)
            if exe is None:
                continue
            try:
                shapes = {n: jax.ShapeDtypeStruct(s, np.dtype(d))
                          for n, s, d in sig}
                # cache_fingerprint, not bare fingerprint: the policy is
                # key material, so an artifact baked under one policy is
                # rejected by a process serving another
                fp = jitted.cache_fingerprint(
                    jitted.lower(shapes, state))
                out[sig] = {"blob":
                            compile_cache.serialize_executable(exe),
                            "fingerprint": fp}
            except Exception:
                continue
        return out

    def adopt_warm(self, entries: Dict[Tuple, Dict]) -> int:
        """Install pre-serialized executables keyed by feed signature
        (the inverse of serialize_warm, called by the serving engine at
        boot): each adopted entry becomes a ready compiled-signature
        cache slot without any XLA compile. Adoption DOES re-lower each
        signature (tracing, milliseconds) to recompute its fingerprint
        against the artifact's: a stale artifact — baked by a paddle_tpu
        whose lowering has since changed, or under different compile
        flags — is rejected per entry and that bucket warms/compiles
        normally. Any malformed, undeserializable, or mismatched entry
        is likewise skipped, never raised: a bad artifact costs a cold
        bucket, not a serving boot. Returns how many signatures
        adopted."""
        from .core import compile_cache

        if self._native is not None:
            return 0
        adopted = 0
        for sig, entry in entries.items():
            try:
                jitted, state = self._compiled(sig, warm=False)
                shapes = {n: jax.ShapeDtypeStruct(s, np.dtype(d))
                          for n, s, d in sig}
                fp = jitted.cache_fingerprint(
                    jitted.lower(shapes, state))
                if fp is None or fp != entry["fingerprint"]:
                    continue  # lowering/flags drifted since the bake
                exe = compile_cache.deserialize_executable(
                    entry["blob"])
                jitted.adopt(exe, shapes, state)
                adopted += 1
            except Exception:
                continue
        return adopted

    def run(self, inputs: Sequence[PaddleTensor]) -> List[PaddleTensor]:
        return self.run_handle(inputs).result()

    def run_handle(self, inputs: Sequence[PaddleTensor]):
        """Dispatch without fetching: returns a lazy
        core.async_exec.FetchHandle whose `.result()` is the
        List[PaddleTensor] `run` would return — pad-slice bucketing
        postprocessing included. The device computes while the caller
        (e.g. the serving Engine) does other host work; resolution
        records the dispatch-to-ready latency. On the native engine
        (no XLA, synchronous by construction) the handle is
        pre-computed."""
        from .core.async_exec import FetchHandle

        if self._native is not None:
            feed = {}
            for i, t in enumerate(inputs):
                name = t.name or self._feed_names[i]
                dt = self._feed_dtypes.get(name)
                # unknown feed names keep their dtype — the engine then
                # raises its clear unknown-var error, like the XLA path
                feed[name] = np.asarray(t.data).astype(dt) if dt \
                    else np.asarray(t.data)
            outs = self._native.run(feed)
            return FetchHandle(
                outs, site="infer",
                transform=lambda arrs: [PaddleTensor(o, name=n)
                                        for n, o in zip(self._fetch_names,
                                                        arrs)])
        feeds = {}
        for i, t in enumerate(inputs):
            name = t.name or self._feed_names[i]
            var = self._find_var(name)
            want = self._policy.feed_dtype(
                np.dtype(normalize_dtype(var.dtype))) \
                if var is not None else None
            arr = np.asarray(t.data)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
            feeds[name] = arr
        # opt-in shape bucketing: pad the batch up to its bucket so the
        # jit cache stays bounded by the bucket set, then slice outputs
        # back to the true batch (rows whose leading dim is the bucket)
        policy = self.config._bucketing
        true_n = bucket = None
        if policy is not None:
            from .serving.bucketing import common_batch

            batched = {k: v for k, v in feeds.items()
                       if self._feed_batched.get(k) is not False}
            n = common_batch(batched) if batched else None
            if n:
                b = policy.bucket_for(n)
                if b is not None and b != n:
                    feeds = {k: (policy.pad_batch(v, b) if k in batched
                                 else v)
                             for k, v in feeds.items()}
                    true_n, bucket = n, b
        sig = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                           for n, v in feeds.items()))
        jitted, state = self._compiled(sig)
        outs = jitted({n: jnp.asarray(v) for n, v in feeds.items()}, state)

        def postprocess(arrs):
            results = []
            for a, name in zip(arrs, self._fetch_names):
                if true_n is not None and a.ndim \
                        and a.shape[0] == bucket \
                        and self._fetch_batched.get(name) is not False:
                    a = a[:true_n]
                results.append(PaddleTensor(a, name=name))
            return results

        return FetchHandle(outs, site="infer", transform=postprocess)

    # numpy-dict convenience API
    def predict(self, **feeds) -> Dict[str, np.ndarray]:
        return self.predict_handle(**feeds).result()

    def predict_handle(self, **feeds):
        """Lazy predict: dispatch now, numpy dict on `.result()`."""
        tensors = [PaddleTensor(v, name=k) for k, v in feeds.items()]
        return self.run_handle(tensors).map(
            lambda ts: {t.name: t.data for t in ts})


def create_paddle_predictor(config: AnalysisConfig) -> Predictor:
    """reference: api/paddle_api.h:346 CreatePaddlePredictor."""
    return Predictor(config)
