"""Precision-policy tests (ISSUE 7): resolution order, the zero-upcast
feed hot path, policy-keyed executor/compile caches, mixed-precision
training on both the fluid and jax-native paths, dynamic loss scaling
(state in TrainState, observability counters/events), checkpoint
round-trip + cross-precision restore safety, and int8 serving."""

import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import optax
import pytest

import paddle_tpu as pt
from paddle_tpu.core import precision
from paddle_tpu.core.executor import _JitDispatch, _normalize_feed
from paddle_tpu.observability import events, telemetry


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _linear_program(lr=0.05):
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[8], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        h = pt.layers.fc(input=x, size=16, act="relu")
        pred = pt.layers.fc(input=h, size=1)
        loss = pt.layers.mean(
            pt.layers.square_error_cost(input=pred, label=y))
        pt.optimizer.SGD(lr).minimize(loss)
    return main, startup, loss


def _train(exe, main, startup, loss, X, Y, steps=25):
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        return [float(np.asarray(
            exe.run(main, feed={"x": X, "y": Y},
                    fetch_list=[loss])[0]).reshape(()))
            for _ in range(steps)]


# ---------------------------------------------------------------------------
# Policy object + resolution order
# ---------------------------------------------------------------------------


def test_policy_registry_and_unknown_name():
    assert precision.get_policy(None).name == "f32"
    assert precision.get_policy("bf16").compute_dtype == np.dtype(
        ml_dtypes.bfloat16)
    assert precision.get_policy("mixed_bf16").dynamic_loss_scale
    assert not precision.get_policy("f32").op_autocast
    with pytest.raises(ValueError, match="unknown precision policy"):
        precision.get_policy("bf17")
    # instances pass through (tests tune hyperparams this way)
    p = precision.PrecisionPolicy("mixed_bf16", compute_dtype="bfloat16",
                                  dynamic_loss_scale=True,
                                  growth_interval=3)
    assert precision.get_policy(p) is p


def test_resolution_order(monkeypatch):
    prog = pt.Program()
    # default: f32
    assert precision.resolve(prog).name == "f32"
    # env
    monkeypatch.setenv("PADDLE_TPU_PRECISION", "bf16")
    assert precision.resolve(prog).name == "bf16"
    # program attr beats env
    precision.set_program_precision(prog, "mixed_bf16")
    assert precision.resolve(prog).name == "mixed_bf16"
    # explicit beats both
    assert precision.resolve(prog, explicit="f32").name == "f32"
    # clearing the attr falls back to env
    precision.set_program_precision(prog, None)
    assert precision.resolve(prog).name == "bf16"
    # a typo'd env fails fast instead of silently meaning f32
    monkeypatch.setenv("PADDLE_TPU_PRECISION", "hf8")
    with pytest.raises(ValueError, match="unknown precision policy"):
        precision.resolve(prog)


def test_set_program_precision_bumps_version():
    prog = pt.Program()
    v0 = prog._version
    precision.set_program_precision(prog, "bf16")
    assert prog._version > v0
    assert precision.program_precision(prog) == "bf16"
    # re-pinning the SAME policy is a no-op: compiled steps stay valid
    v1 = prog._version
    precision.set_program_precision(prog, "bf16")
    assert prog._version == v1
    precision.set_program_precision(prog, "mixed_bf16")
    assert prog._version > v1


# ---------------------------------------------------------------------------
# Feed normalization: the zero-upcast hot path
# ---------------------------------------------------------------------------


def test_bf16_feed_passes_untouched_under_bf16_policies():
    main, _, _ = _linear_program()
    xb = jnp.asarray(np.ones((4, 8), ml_dtypes.bfloat16))
    for pol in ("bf16", "mixed_bf16"):
        out = _normalize_feed(main, {"x": xb}, precision.get_policy(pol))
        # the exact acceptance criterion: NO astype of a bf16 feed on
        # the hot path — the same array object comes back
        assert out["x"] is xb
    # under f32 the same feed upcasts (the declared f32 width wins)
    out = _normalize_feed(main, {"x": xb}, precision.get_policy("f32"))
    assert out["x"].dtype == np.float32


def test_f32_feed_downcasts_once_and_ints_untouched():
    main, _, _ = _linear_program()
    pol = precision.get_policy("mixed_bf16")
    xf = np.ones((4, 8), np.float32)
    out = _normalize_feed(main, {"x": xf}, pol)
    assert out["x"].dtype == ml_dtypes.bfloat16
    # integer feeds keep their canonical dtype under every policy
    assert pol.feed_dtype(np.dtype(np.int64)) == np.dtype(np.int64)
    assert pol.feed_dtype(np.dtype(np.float32)) == np.dtype(
        ml_dtypes.bfloat16)


# ---------------------------------------------------------------------------
# Fluid path: training parity + policy-keyed program cache
# ---------------------------------------------------------------------------


def test_fluid_mixed_bf16_matches_f32_trajectory(rng):
    X = rng.rand(16, 8).astype("float32")
    Y = (X @ rng.rand(8, 1)).astype("float32")
    exe = pt.Executor(pt.CPUPlace())
    main, startup, loss = _linear_program()
    f32 = _train(exe, main, startup, loss, X, Y)
    precision.set_program_precision(main, "mixed_bf16")
    mixed = _train(exe, main, startup, loss, X, Y)
    precision.set_program_precision(main, None)
    assert f32[-1] < f32[0] * 0.5
    assert mixed[-1] < mixed[0] * 0.5
    # stated parity bound: every step within 5% relative of f32
    for a, b in zip(mixed, f32):
        assert abs(a - b) <= 0.05 * max(1.0, abs(b)), (a, b)


def test_fluid_pure_bf16_trains_and_stores_bf16_state(rng):
    X = rng.rand(16, 8).astype("float32")
    Y = (X @ rng.rand(8, 1)).astype("float32")
    exe = pt.Executor(pt.CPUPlace())
    main, startup, loss = _linear_program()
    precision.set_program_precision(main, "bf16")
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        losses = [float(np.asarray(
            exe.run(main, feed={"x": X, "y": Y},
                    fetch_list=[loss])[0]).reshape(()))
            for _ in range(25)]
    precision.set_program_precision(main, None)
    assert losses[-1] < losses[0] * 0.5
    # pure bf16: params live at the compute width after the first step
    w = next(v for b in main.desc.blocks for v in b.vars
             if v.endswith(".w_0"))
    assert np.asarray(scope.find_var(w)).dtype == ml_dtypes.bfloat16


def test_policy_flip_recompiles_program_cache(rng):
    X = rng.rand(4, 8).astype("float32")
    Y = (X @ rng.rand(8, 1)).astype("float32")
    exe = pt.Executor(pt.CPUPlace())
    main, startup, loss = _linear_program()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        misses0 = exe.cache_stats()["misses"]
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        assert exe.cache_stats()["misses"] == misses0  # steady state hits
        precision.set_program_precision(main, "mixed_bf16")
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        assert exe.cache_stats()["misses"] == misses0 + 1
    precision.set_program_precision(main, None)


# ---------------------------------------------------------------------------
# _JitDispatch: policy in the signature + the persistent-cache key
# ---------------------------------------------------------------------------


def test_jit_dispatch_policy_in_signature_and_fingerprint():
    def f(a):
        return a * 2

    d32 = _JitDispatch(jax.jit(f), "step")
    db16 = _JitDispatch(jax.jit(f), "step", policy="bf16")
    x = jnp.ones((2, 2), jnp.float32)
    assert d32._aval_sig((x,))[0] == "f32"
    assert db16._aval_sig((x,))[0] == "bf16"
    assert d32._aval_sig((x,)) != db16._aval_sig((x,))
    # same lowered module, different policies → different persistent
    # cache keys (flip policy → guaranteed miss, never a stale-policy
    # executable)
    low = jax.jit(f).lower(x)
    assert d32.cache_fingerprint(low) != db16.cache_fingerprint(low)
    assert db16._meta["policy"] == "bf16"
    # f32 keys are byte-identical to the pre-policy (PR 6) keys: the
    # upgrade must not invalidate every warm cache dir and artifact
    from paddle_tpu.core import compile_cache
    assert d32.cache_fingerprint(low) == compile_cache.fingerprint(low)


def test_compile_cache_policy_separation(tmp_path, monkeypatch, rng):
    """Satellite: same program under f32 vs bf16 produces DISTINCT
    on-disk cache entries, and a policy flip on a warm cache recompiles
    (miss+store) instead of hitting."""
    cache_dir = tmp_path / "jexcache"
    cache_dir.mkdir()
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", str(cache_dir))
    X = rng.rand(4, 8).astype("float32")
    Y = (X @ rng.rand(8, 1)).astype("float32")
    main, startup, loss = _linear_program()

    def entries():
        return {p for p in os.listdir(cache_dir) if p.endswith(".jex")}

    def run_fresh_executor():
        exe = pt.Executor(pt.CPUPlace())
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])

    def counts():
        return {ev: telemetry.COMPILE_CACHE.value(kind="step", event=ev)
                for ev in ("hit", "store")}

    c0 = counts()
    run_fresh_executor()
    f32_entries = entries()
    assert f32_entries, "f32 run stored no cache entries"
    c1 = counts()
    n_startup_entries = 1  # the startup program's own (policy-free) step

    precision.set_program_precision(main, "bf16")
    run_fresh_executor()
    bf16_entries = entries() - f32_entries
    # distinct on-disk entries per policy, and the flipped run COMPILED
    # (stored fresh entries) rather than deserializing an f32-policy
    # executable; only the startup program (not under the policy) may
    # hit its own warm entry
    assert bf16_entries, "bf16 run reused the f32 entries"
    c2 = counts()
    assert c2["store"] > c1["store"]
    assert c2["hit"] - c1["hit"] <= n_startup_entries

    # warm cache, same policy → the main program now hits too
    run_fresh_executor()
    c3 = counts()
    assert c3["hit"] - c2["hit"] > n_startup_entries
    assert c3["store"] == c2["store"]
    precision.set_program_precision(main, None)


# ---------------------------------------------------------------------------
# jax-native path: mixed step, loss scaling, TrainState, checkpointing
# ---------------------------------------------------------------------------


def _mesh():
    from paddle_tpu.parallel import MeshConfig, make_mesh

    return make_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])


def _loss_fn(p, b, r):
    return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)


_AXES = {"w": ("io", "model"), "b": ("model",)}


def _fresh_params():
    r = np.random.RandomState(1)
    return {"w": jnp.asarray(r.rand(8, 4), jnp.float32),
            "b": jnp.zeros((4,), jnp.float32)}


def _make(mesh, precision_arg):
    from paddle_tpu.parallel.train import make_train_step

    return make_train_step(_loss_fn, optax.sgd(0.05), mesh, _AXES,
                           precision=precision_arg)


def _batch(rng):
    X = rng.rand(16, 8).astype("float32")
    return {"x": X, "y": (X @ rng.rand(8, 4)).astype("float32")}


def test_native_mixed_bf16_parity_and_state_widths(rng):
    from paddle_tpu.parallel import mesh_guard

    mesh = _mesh()
    batch = _batch(rng)
    results = {}
    with mesh_guard(mesh):
        for pol in ("f32", "mixed_bf16", "bf16"):
            init, step = _make(mesh, pol)
            st = init(_fresh_params())
            losses = []
            for i in range(15):
                st, l = step(st, batch, jax.random.key(i))
                losses.append(float(l))
            results[pol] = (st, losses)
    st32, l32 = results["f32"]
    stm, lm = results["mixed_bf16"]
    stb, lb = results["bf16"]
    assert l32[-1] < l32[0] and lm[-1] < lm[0] and lb[-1] < lb[0]
    for a, b in zip(lm, l32):
        assert abs(a - b) <= 0.05 * max(1.0, abs(b))
    # mixed: f32 master params + loss-scale state; pure bf16: bf16
    # params, no scaling state
    assert stm.params["w"].dtype == jnp.float32
    assert stm.loss_scale is not None
    assert int(stm.loss_scale["overflows"]) == 0
    assert stb.params["w"].dtype == ml_dtypes.bfloat16
    assert stb.loss_scale is None and st32.loss_scale is None


def test_dynamic_loss_scale_overflow_skip_and_growth(rng):
    from paddle_tpu.parallel import mesh_guard

    mesh = _mesh()
    batch = _batch(rng)
    pol = precision.PrecisionPolicy(
        "mixed_bf16", compute_dtype="bfloat16", op_autocast=True,
        dynamic_loss_scale=True, init_loss_scale=1024.0,
        growth_interval=3)
    bad = {"x": np.full((16, 8), np.inf, "float32"), "y": batch["y"]}
    with mesh_guard(mesh):
        init, step = _make(mesh, pol)
        st = init(_fresh_params())
        w0 = np.asarray(st.params["w"])
        st1, l1 = step(st, bad, jax.random.key(0))
        # overflow: update skipped (params + opt state untouched),
        # scale halves, counter ticks
        assert not np.isfinite(float(l1))
        assert np.array_equal(w0, np.asarray(st1.params["w"]))
        assert float(st1.loss_scale["scale"]) == 512.0
        assert int(st1.loss_scale["overflows"]) == 1
        assert int(st1.loss_scale["good_steps"]) == 0
        # growth_interval clean steps grow the scale back
        for i in range(3):
            st1, _ = step(st1, batch, jax.random.key(1 + i))
        assert float(st1.loss_scale["scale"]) == 1024.0
        assert int(st1.loss_scale["growths"]) == 1


def test_amp_metrics_and_events_via_train_loop(rng):
    from paddle_tpu.parallel import mesh_guard
    from paddle_tpu.parallel.train import train_loop

    mesh = _mesh()
    batch = _batch(rng)
    bad = {"x": np.full((16, 8), np.inf, "float32"), "y": batch["y"]}
    events.clear()
    over0 = telemetry.AMP_EVENTS.value(event="overflow")
    skip0 = telemetry.AMP_EVENTS.value(event="skip")

    def batches(step):
        if step >= 5:
            return None
        return bad if step == 2 else batch

    with mesh_guard(mesh):
        init, step = _make(mesh, "mixed_bf16")
        st = init(_fresh_params())
        st, losses, stop = train_loop(step, st, batches, fetch_window=1)
    assert stop == "completed"
    assert telemetry.AMP_EVENTS.value(event="overflow") == over0 + 1
    assert telemetry.AMP_EVENTS.value(event="skip") == skip0 + 1
    evs = events.recent(20, kind="amp_overflow")
    assert evs and evs[-1]["count"] == 1
    # sync mode attributes the overflow to its exact step
    assert evs[-1]["step"] == 3  # state.step AFTER the offending step
    assert telemetry.AMP_LOSS_SCALE.value() == float(
        st.loss_scale["scale"])


def test_loss_scale_checkpoint_roundtrip_bit_identical(rng, tmp_path):
    from paddle_tpu.parallel import mesh_guard
    from paddle_tpu.resilience import CheckpointManager

    mesh = _mesh()
    batch = _batch(rng)
    with mesh_guard(mesh):
        init, step = _make(mesh, "mixed_bf16")
        st = init(_fresh_params())
        for i in range(3):
            st, _ = step(st, batch, jax.random.key(i))
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(st)
        back = mgr.restore_latest(init(_fresh_params()))
    for a, b in zip(jax.tree.leaves(st.params),
                    jax.tree.leaves(back.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).dtype == np.asarray(b).dtype
    for k in ("scale", "good_steps", "overflows", "growths"):
        assert np.array_equal(np.asarray(st.loss_scale[k]),
                              np.asarray(back.loss_scale[k])), k


def test_cross_precision_restore_fails_or_casts_explicitly(rng, tmp_path):
    """Satellite: a bf16 checkpoint into an f32 template (and vice
    versa) either fails with a clear error or casts EXPLICITLY — never
    silently mixes widths."""
    from paddle_tpu.parallel import mesh_guard
    from paddle_tpu.parallel.checkpoint import (PrecisionMismatchError,
                                                restore_train_state,
                                                save_train_state)

    mesh = _mesh()
    with mesh_guard(mesh):
        init_b, step_b = _make(mesh, "bf16")
        st_b = init_b(_fresh_params())
        p = str(tmp_path / "bf16ck")
        save_train_state(p, st_b)
        init_32, _ = _make(mesh, "f32")
        tmpl = init_32(_fresh_params())
        with pytest.raises(PrecisionMismatchError,
                           match="different precision"):
            restore_train_state(p, tmpl)
        casted = restore_train_state(p, tmpl, cast_dtypes=True)
        assert casted.params["w"].dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(casted.params["w"]),
            np.asarray(st_b.params["w"], dtype=np.float32))
        # and the other direction: f32 checkpoint into a bf16 template
        init_32b, _ = _make(mesh, "f32")
        st32 = init_32b(_fresh_params())
        p2 = str(tmp_path / "f32ck")
        save_train_state(p2, st32)
        tmpl_b = init_b(_fresh_params())
        with pytest.raises(PrecisionMismatchError):
            restore_train_state(p2, tmpl_b)


def test_cross_policy_loss_scale_structure(rng, tmp_path):
    """Loss-scale PRESENCE differing between checkpoint and template is
    itself a cross-precision restore: a clear PrecisionMismatchError,
    or an explicit reshard under cast_dtypes=True (checkpoint-side
    state dropped / template's fresh init kept) — never an opaque
    orbax tree-structure error."""
    from paddle_tpu.parallel import mesh_guard
    from paddle_tpu.parallel.checkpoint import (PrecisionMismatchError,
                                                restore_train_state,
                                                save_train_state)

    mesh = _mesh()
    batch = _batch(rng)
    with mesh_guard(mesh):
        init_m, step_m = _make(mesh, "mixed_bf16")
        st_m = init_m(_fresh_params())
        st_m, _ = step_m(st_m, batch, jax.random.key(0))
        p = str(tmp_path / "mixedck")
        save_train_state(p, st_m)
        # mixed checkpoint (loss_scale present) into an f32 template
        init_32, _ = _make(mesh, "f32")
        tmpl32 = init_32(_fresh_params())
        with pytest.raises(PrecisionMismatchError,
                           match="loss-scaling"):
            restore_train_state(p, tmpl32)
        got = restore_train_state(p, tmpl32, cast_dtypes=True)
        assert got.loss_scale is None
        np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                      np.asarray(st_m.params["w"]))
        # f32 checkpoint (no loss_scale) into a mixed template
        st32 = init_32(_fresh_params())
        p2 = str(tmp_path / "f32ck2")
        save_train_state(p2, st32)
        tmpl_m = init_m(_fresh_params())
        with pytest.raises(PrecisionMismatchError,
                           match="loss-scaling"):
            restore_train_state(p2, tmpl_m)
        got2 = restore_train_state(p2, tmpl_m, cast_dtypes=True)
        assert got2.loss_scale is not None  # template's fresh init
        assert float(got2.loss_scale["scale"]) == float(
            tmpl_m.loss_scale["scale"])
        np.testing.assert_array_equal(np.asarray(got2.params["w"]),
                                      np.asarray(st32.params["w"]))


# ---------------------------------------------------------------------------
# Serving: int8 path + accuracy delta, bf16 policy serving
# ---------------------------------------------------------------------------


def _save_serving_model(tmp_path):
    md = str(tmp_path / "model")
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[4], dtype="float32")
        pred = pt.layers.fc(input=x, size=3, act="softmax")
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        pt.io.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main)
    return md


def test_int8_serving_engine_end_to_end(rng, tmp_path):
    from paddle_tpu.serving import Engine, ServingConfig

    md = _save_serving_model(tmp_path)
    cal = [{"x": rng.rand(2, 4).astype("float32")} for _ in range(4)]
    scale0 = telemetry._m.snapshot().get("paddle_tpu_quant_scale")
    events.clear()
    cfg = ServingConfig(md, buckets=(1, 2, 4), use_tpu=False,
                        precision="int8", calibration=lambda: iter(cal))
    eng = Engine(cfg)
    assert eng.warmup() == 3  # per-bucket quantized executables
    X = rng.rand(2, 4).astype("float32")
    out = eng.run_batch({"x": X})
    (name, reply), = out.items()
    assert reply.dtype == np.float32  # dequantized f32 replies
    assert reply.shape == (2, 3)

    e32 = Engine(ServingConfig(md, buckets=(1, 2, 4), use_tpu=False))
    e32.warmup()
    ref = e32.run_batch({"x": X})[name]
    assert float(np.abs(reply - ref).max()) <= 0.05

    st = eng.status()
    assert st["precision"] == "int8"
    assert st["accuracy_delta"]["max_abs"] <= 0.05
    assert st["accuracy_delta"]["batches"] == 4
    # calibration stats flowed through the metrics registry + event log
    snap = telemetry._m.snapshot()
    series = snap["paddle_tpu_quant_scale"]["series"]
    acts = [s for s in series if s["labels"].get("kind") == "activation"]
    assert acts and acts[0]["count"] >= 1
    kinds = {e["action"] for e in events.recent(50, kind="quantize")}
    assert {"calibrate", "weights", "serving_calibrate",
            "accuracy_check"} <= kinds


def test_int8_serving_requires_calibration(tmp_path):
    from paddle_tpu.serving import Engine, ServingConfig

    md = _save_serving_model(tmp_path)
    with pytest.raises(ValueError, match="calibration"):
        Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False,
                             precision="int8"))
    # externally built predictors cannot be post-training quantized
    acfg = pt.AnalysisConfig(md)
    acfg.disable_gpu()
    pred = pt.create_paddle_predictor(acfg)
    with pytest.raises(ValueError, match="externally built predictor"):
        Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False,
                             precision="int8",
                             calibration=lambda: iter([])),
               predictor=pred)


def test_int8_serving_reuses_quantized_sibling(rng, tmp_path):
    from paddle_tpu.serving import Engine, ServingConfig

    md = _save_serving_model(tmp_path)
    cal = [{"x": rng.rand(2, 4).astype("float32")} for _ in range(2)]
    Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False,
                         precision="int8", calibration=lambda: iter(cal)))
    # second boot without calibration reuses the .int8 sibling
    eng = Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False,
                               precision="int8"))
    out = eng.run_batch({"x": rng.rand(2, 4).astype("float32")})
    assert next(iter(out.values())).shape == (2, 3)


def test_int8_sibling_reuse_with_calibration_configured(rng, tmp_path):
    """Static configs keep calibration= set on every boot — a restart
    must reuse the sibling quantized from THIS program instead of
    paying a full recalibration, and a sibling from a different
    program must NOT be reused."""
    from paddle_tpu.serving import Engine, ServingConfig
    from paddle_tpu.serving.engine import QUANT_SRC_FILE

    md = _save_serving_model(tmp_path)
    cal = [{"x": rng.rand(2, 4).astype("float32")} for _ in range(2)]

    def mk():
        return ServingConfig(md, buckets=(1, 2), use_tpu=False,
                             precision="int8",
                             calibration=lambda: iter(cal),
                             accuracy_check_batches=0)

    Engine(mk())
    events.clear()
    Engine(mk())  # same static config on "restart": no recalibration
    actions = [e["action"] for e in events.recent(50, kind="quantize")]
    assert "serving_reuse" in actions
    assert "serving_calibrate" not in actions
    # source digest disagrees → the sibling is requantized
    with open(os.path.join(md + ".int8", QUANT_SRC_FILE), "w") as f:
        f.write('{"source_model_digest": "not-this-program"}')
    events.clear()
    Engine(mk())
    actions = [e["action"] for e in events.recent(50, kind="quantize")]
    assert "serving_calibrate" in actions
    # ...and WITHOUT calibration a stale sibling is an error, never
    # silently served with the old model's weights
    with open(os.path.join(md + ".int8", QUANT_SRC_FILE), "w") as f:
        f.write('{"source_model_digest": "not-this-program"}')
    with pytest.raises(ValueError, match="different model"):
        Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False,
                             precision="int8"))


def test_serving_explicit_precision_wins_over_env(rng, tmp_path,
                                                  monkeypatch):
    """ServingConfig precision beats PADDLE_TPU_PRECISION (resolution
    order: explicit first): an f32 engine under an ambient bf16 env
    still serves f32 executables and f32 replies."""
    from paddle_tpu.serving import Engine, ServingConfig

    md = _save_serving_model(tmp_path)
    monkeypatch.setenv("PADDLE_TPU_PRECISION", "bf16")
    eng = Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False))
    X = rng.rand(2, 4).astype("float32")
    out = eng.run_batch({"x": X})
    assert next(iter(out.values())).dtype == np.float32


def test_bf16_serving_policy(rng, tmp_path):
    from paddle_tpu.serving import Engine, ServingConfig

    md = _save_serving_model(tmp_path)
    eng = Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False,
                               precision="bf16"))
    assert eng.warmup() == 2
    X = rng.rand(2, 4).astype("float32")
    out = eng.run_batch({"x": X})
    (name, reply), = out.items()
    assert reply.dtype == ml_dtypes.bfloat16
    e32 = Engine(ServingConfig(md, buckets=(1, 2), use_tpu=False))
    ref = e32.run_batch({"x": X})[name]
    assert float(np.abs(np.asarray(reply, np.float32)
                        - ref).max()) <= 0.05
    assert eng.status()["precision"] == "bf16"
    with pytest.raises(ValueError, match="unknown precision policy"):
        ServingConfig(md, precision="int4")


def test_serving_config_unknown_precision_fails_fast(tmp_path):
    from paddle_tpu.serving import ServingConfig

    with pytest.raises(ValueError, match="unknown precision policy"):
        ServingConfig(str(tmp_path), precision="fp8")
    # a VALID policy the serving engine does not implement must also
    # fail fast, not silently serve f32 under a mislabeled status
    with pytest.raises(ValueError, match="unknown precision policy"):
        ServingConfig(str(tmp_path), precision="mixed_f16")
