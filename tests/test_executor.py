"""Executor + Scope tests (reference analogues:
test_executor_and_use_program_cache.py, test_exe*.py)."""

import numpy as np
import pytest

import paddle_tpu as pt


def _linreg_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[13], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        pred = pt.layers.fc(input=x, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(input=pred, label=y))
        pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def test_linreg_converges(rng):
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(64, 13).astype("float32")
    Y = (X @ rng.rand(13, 1)).astype("float32")
    losses = [float(exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])[0])
              for _ in range(60)]
    assert losses[-1] < losses[0] * 0.05


def test_program_cache_and_recompile(rng):
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(16, 13).astype("float32")
    Y = rng.rand(16, 1).astype("float32")
    exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
    n_cached = len(exe._cache)
    exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
    assert len(exe._cache) == n_cached  # same signature reused
    # different batch size -> new specialization
    exe.run(main, feed={"x": X[:8], "y": Y[:8]}, fetch_list=[loss])
    assert len(exe._cache) == n_cached + 1


def test_scope_isolation(rng):
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    s1, s2 = pt.Scope(), pt.Scope()
    X = rng.rand(8, 13).astype("float32")
    Y = rng.rand(8, 1).astype("float32")
    param_names = [v.name for v in main.list_vars() if isinstance(v, pt.Parameter)]
    with pt.scope_guard(s1):
        exe.run(startup)
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        w1 = {n: np.array(s1.get(n)) for n in param_names}
    with pt.scope_guard(s2):
        exe.run(startup)
        for _ in range(10):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        w2 = {n: np.array(s2.get(n)) for n in param_names}
    # s1 params untouched by s2 training
    for n in param_names:
        np.testing.assert_array_equal(np.array(s1.get(n)), w1[n])
        assert not np.array_equal(w1[n], w2[n])


def test_fetch_variable_and_missing_feed_error(rng):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[3], dtype="float32")
        out = pt.layers.scale(x, scale=2.0)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(4, 3).astype("float32")
    res = exe.run(main, feed={"x": X}, fetch_list=[out])[0]
    np.testing.assert_allclose(res, X * 2.0, rtol=1e-6)
    with pytest.raises(Exception):
        exe.run(main, feed={}, fetch_list=[out])


def test_rng_determinism():
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 42
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[100], dtype="float32")
        out = pt.layers.dropout(x, dropout_prob=0.5)
    exe = pt.Executor(pt.CPUPlace())
    X = np.ones((4, 100), "float32")

    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        a = exe.run(main, feed={"x": X}, fetch_list=[out])[0]
        b = exe.run(main, feed={"x": X}, fetch_list=[out])[0]
    # rng state advances between steps
    assert not np.array_equal(a, b)
    # fresh scope with same seed replays the same stream
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        a2 = exe.run(main, feed={"x": X}, fetch_list=[out])[0]
    np.testing.assert_array_equal(a, a2)


def test_step2_recompiles_nothing(rng):
    """VERDICT r4 item 7: after the first run of a (program, feed-sig)
    pair, later steps must hit BOTH cache levels — the executor's
    program cache AND the jitted step's executable cache (no retrace,
    no recompile)."""
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    X = rng.rand(16, 13).astype("float32")
    Y = rng.rand(16, 1).astype("float32")
    for _ in range(4):
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
    stats = exe.cache_stats()
    # one miss for startup, one for the first main step; steps 2-4 hit
    assert stats["misses"] == 2 and stats["hits"] == 3, stats
    assert stats["entries"] == 2, stats
    (step,) = [s for s in exe._cache.values() if s.fetch_names]
    # the jit layer compiled exactly one executable for the 4 runs
    assert step.fn._cache_size() == 1


def test_run_chained_matches_sequential(rng):
    """Scan-chained fast path: n steps in ONE dispatch must leave the
    scope in the same state as n sequential run() calls and return the
    same per-step losses (identical op sequence => identical floats on
    CPU)."""
    X = rng.rand(32, 13).astype("float32")
    Y = (X @ rng.rand(13, 1)).astype("float32")

    def train(n_steps, chained):
        pt.framework.unique_name.generator = \
            pt.framework.UniqueNameGenerator()
        main, startup, loss = _linreg_program()
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            if chained:
                losses = exe.run_chained(main, feed={"x": X, "y": Y},
                                         fetch_list=[loss],
                                         n_steps=n_steps)[0]
                losses = [float(v) for v in np.asarray(losses).ravel()]
            else:
                losses = [float(exe.run(main, feed={"x": X, "y": Y},
                                        fetch_list=[loss])[0])
                          for _ in range(n_steps)]
            params = {v.name: np.array(scope.get(v.name))
                      for v in main.list_vars()
                      if isinstance(v, pt.Parameter)}
        return losses, params

    seq_losses, seq_params = train(5, chained=False)
    ch_losses, ch_params = train(5, chained=True)
    np.testing.assert_allclose(ch_losses, seq_losses, rtol=1e-6)
    assert seq_params.keys() == ch_params.keys()
    for name in seq_params:
        np.testing.assert_allclose(ch_params[name], seq_params[name],
                                   rtol=1e-5, atol=1e-7)
    # chained executable is cached per n_steps: a second call reuses it
    exe = pt.Executor(pt.CPUPlace())
    pt.framework.unique_name.generator = pt.framework.UniqueNameGenerator()
    main, startup, loss = _linreg_program()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.run_chained(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                        n_steps=3)
        exe.run_chained(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                        n_steps=3)
        (step,) = [s for s in exe._cache.values() if s.fetch_names]
        assert step.chained_fn(3)._cache_size() == 1


def test_run_chained_per_step_feeds_matches_sequential(rng):
    """per_step_feeds: a whole data chunk (leading [n_steps] axis) trains
    in ONE dispatch; per-step losses and final params must match n
    sequential run() calls on the individual batches."""
    n, bs = 4, 16
    Xs = rng.rand(n, bs, 13).astype("float32")
    W = rng.rand(13, 1)
    Ys = np.einsum("nbi,io->nbo", Xs, W).astype("float32")

    def train(chained):
        pt.framework.unique_name.generator = \
            pt.framework.UniqueNameGenerator()
        main, startup, loss = _linreg_program()
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            if chained:
                losses = exe.run_chained(
                    main, feed={"x": Xs, "y": Ys}, fetch_list=[loss],
                    n_steps=n, per_step_feeds=True)[0]
                losses = [float(v) for v in np.asarray(losses).ravel()]
            else:
                losses = [float(exe.run(main,
                                        feed={"x": Xs[i], "y": Ys[i]},
                                        fetch_list=[loss])[0])
                          for i in range(n)]
            params = {v.name: np.array(scope.get(v.name))
                      for v in main.list_vars()
                      if isinstance(v, pt.Parameter)}
        return losses, params

    seq_losses, seq_params = train(False)
    ch_losses, ch_params = train(True)
    np.testing.assert_allclose(ch_losses, seq_losses, rtol=1e-6)
    for name in seq_params:
        np.testing.assert_allclose(ch_params[name], seq_params[name],
                                   rtol=1e-5, atol=1e-7)
    # wrong leading axis is a clear error, not a cryptic trace failure
    exe = pt.Executor(pt.CPUPlace())
    pt.framework.unique_name.generator = pt.framework.UniqueNameGenerator()
    main, startup, loss = _linreg_program()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        with pytest.raises(ValueError, match="leading"):
            exe.run_chained(main, feed={"x": Xs[0], "y": Ys[0]},
                            fetch_list=[loss], n_steps=n,
                            per_step_feeds=True)


def test_run_chained_windowed_matches_sequential(rng):
    """unroll="auto" past _UNROLL_WINDOW_MAX on CPU splits the run into
    unrolled windows (the rolled-scan demotion):
    per-step losses, final params, AND the rng stream must match n
    sequential run() calls exactly — windowing is an execution detail,
    not a semantic."""
    from paddle_tpu.core.executor import _UNROLL_WINDOW_MAX

    n_steps = _UNROLL_WINDOW_MAX + 3        # forces 2 windows
    X = rng.rand(16, 13).astype("float32")
    Y = (X @ rng.rand(13, 1)).astype("float32")

    def train(chained):
        pt.framework.unique_name.generator = \
            pt.framework.UniqueNameGenerator()
        main, startup, loss = _linreg_program()
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            if chained:
                losses = exe.run_chained(main, feed={"x": X, "y": Y},
                                         fetch_list=[loss],
                                         n_steps=n_steps)[0]
                losses = [float(v) for v in np.asarray(losses).ravel()]
            else:
                losses = [float(np.asarray(
                    exe.run(main, feed={"x": X, "y": Y},
                            fetch_list=[loss])[0]).reshape(()))
                    for _ in range(n_steps)]
            params = {v.name: np.array(scope.get(v.name))
                      for v in main.list_vars()
                      if isinstance(v, pt.Parameter)}
        return losses, params

    seq_losses, seq_params = train(False)
    ch_losses, ch_params = train(True)
    assert len(ch_losses) == n_steps
    np.testing.assert_allclose(ch_losses, seq_losses, rtol=1e-6)
    for name in seq_params:
        np.testing.assert_allclose(ch_params[name], seq_params[name],
                                   rtol=1e-5, atol=1e-7)
