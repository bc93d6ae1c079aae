"""Host-overlap execution tests (core/async_exec.py + the streaming
drivers).

Ladder: unit (FetchHandle laziness, InFlightWindow bound, Prefetcher
lifecycle) → executor integration (run_stream vs per-step equivalence,
in-flight device-buffer cap via live-array accounting) → driver
integration (streaming train_from_dataset, async train_loop, preemption
at a step boundary mid-window + CheckpointManager resume).
"""

import threading
import time

import numpy as np
import pytest


import paddle_tpu as pt  # noqa: E402
from paddle_tpu.core import async_exec  # noqa: E402
from paddle_tpu.observability import health  # noqa: E402
from paddle_tpu.resilience import faults, preemption  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FAULT_SPEC", raising=False)
    monkeypatch.delenv("PADDLE_TPU_CHECK_NUMERICS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_STREAM_WINDOW", raising=False)
    monkeypatch.delenv("PADDLE_TPU_DEVICE_PREFETCH", raising=False)
    faults.reset()
    preemption.reset()
    health.reset()
    async_exec.reset_inflight_stats()
    yield
    faults.reset()
    preemption.uninstall()
    preemption.reset()
    health.reset()


def _linreg_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[13], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        pred = pt.layers.fc(input=x, size=1)
        loss = pt.layers.mean(
            pt.layers.square_error_cost(input=pred, label=y))
        pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _feeds(rng, n, bs=8):
    W = rng.rand(13, 1)
    out = []
    for _ in range(n):
        X = rng.rand(bs, 13).astype("float32")
        out.append({"x": X, "y": (X @ W).astype("float32")})
    return out


def _no_prefetch_threads():
    return not any(t.name.startswith("paddle-tpu-prefetch")
                   for t in threading.enumerate() if t.is_alive())


# ---------------------------------------------------------------------------
# FetchHandle / InFlightWindow units
# ---------------------------------------------------------------------------


def test_fetch_handle_lazy_and_released():
    import jax.numpy as jnp

    v = jnp.arange(6.0).reshape(2, 3)
    h = async_exec.FetchHandle([v, v + 1], site="unit")
    assert h.raw() is not None
    out = h.result()
    assert isinstance(out[0], np.ndarray)
    np.testing.assert_allclose(out[1], np.arange(6.0).reshape(2, 3) + 1)
    # device refs dropped after resolve; numpy result cached
    assert h.raw() is None
    assert h.result() is out
    # numpy interop on a single-value handle
    h2 = async_exec.FetchHandle([jnp.float32(4.0)])
    assert float(np.asarray(h2)) == 4.0


def test_fetch_handle_transform():
    h = async_exec.FetchHandle([np.arange(4)],
                               transform=lambda arrs: {"sum": arrs[0].sum()})
    assert h.result() == {"sum": 6}


def test_inflight_window_bounds_unresolved_handles():
    import jax.numpy as jnp

    win = async_exec.InFlightWindow(limit=2)
    handles = []
    for i in range(6):
        h = async_exec.FetchHandle([jnp.zeros(3) + i])
        win.admit(h)
        handles.append(h)
        assert sum(1 for x in handles if not x._resolved) <= 2
    assert win.high_water <= 2
    # oldest were force-resolved in admission order
    assert handles[0]._resolved and handles[1]._resolved
    win.drain()
    assert all(h._resolved for h in handles)


# ---------------------------------------------------------------------------
# Prefetcher lifecycle (the reader.py producer-thread fix)
# ---------------------------------------------------------------------------


def test_prefetcher_basic_and_joined_on_exhaustion():
    pf = async_exec.Prefetcher(iter(range(10)), depth=3)
    assert list(pf) == list(range(10))
    pf.thread.join(timeout=5)
    assert not pf.thread.is_alive()


def test_prefetcher_error_propagates():
    def gen():
        yield 1
        raise RuntimeError("boom-in-producer")

    pf = async_exec.Prefetcher(gen(), depth=2)
    it = iter(pf)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="boom-in-producer"):
        next(it)
    pf.thread.join(timeout=5)
    assert not pf.thread.is_alive()


def test_prefetcher_early_close_joins_thread():
    def endless():
        i = 0
        while True:
            yield i
            i += 1

    pf = async_exec.Prefetcher(endless(), depth=2)
    it = iter(pf)
    assert next(it) == 0
    pf.close()
    assert not pf.thread.is_alive()
    pf.close()  # idempotent


def test_loader_producer_error_propagates():
    loader = pt.DataLoader.from_generator(feed_list=[], capacity=4)

    def bad():
        yield {"x": np.ones((2, 3), "float32")}
        raise ValueError("generator exploded")

    loader.set_batch_generator(bad)
    got = []
    with pytest.raises(ValueError, match="generator exploded"):
        for b in loader():
            got.append(b)
    assert len(got) == 1
    deadline = time.time() + 5
    while not _no_prefetch_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert _no_prefetch_threads()


def test_loader_early_exit_joins_producer():
    loader = pt.DataLoader.from_generator(feed_list=[], capacity=2)

    def gen():
        for i in range(1000):
            yield {"x": np.full((2, 2), i, "float32")}

    loader.set_batch_generator(gen)
    for i, b in enumerate(loader()):
        if i == 2:
            break
    deadline = time.time() + 5
    while not _no_prefetch_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert _no_prefetch_threads()


def test_loader_device_prefetch_gating(monkeypatch):
    import jax

    def build():
        loader = pt.DataLoader.from_generator(feed_list=[], capacity=4)

        def gen():
            for i in range(3):
                yield {"x": np.full((4, 2), i, "float32")}

        loader.set_batch_generator(gen, places=[pt.CPUPlace()])
        return loader

    # CPU places: no transfer to hide — batches stay numpy (existing
    # consumers may mutate them in place)
    batches = list(build()())
    assert isinstance(batches[0]["x"], np.ndarray)
    # explicit opt-in: the double-buffer stage device_puts ahead of use
    monkeypatch.setenv("PADDLE_TPU_DEVICE_PREFETCH", "1")
    batches = list(build()())
    assert len(batches) == 3
    assert isinstance(batches[0]["x"], jax.Array)
    np.testing.assert_allclose(np.asarray(batches[2]["x"]), 2.0)


def test_mesh_device_put_shards_divisible_leading_dim():
    import jax
    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard

    mesh = make_mesh(MeshConfig(dp=-1))
    with mesh_guard(mesh):
        out = async_exec.mesh_device_put(
            {"a": np.zeros((8 * mesh.shape["dp"], 3), "float32"),
             "b": np.zeros((3,), "float32")})
    n = mesh.shape["dp"]
    assert len(out["a"].sharding.device_set) == n
    # indivisible/low-rank leaves replicate rather than erroring
    assert len(out["b"].devices()) in (1, n)


# ---------------------------------------------------------------------------
# run_stream: equivalence + device-buffer cap
# ---------------------------------------------------------------------------


def test_run_stream_matches_per_step(rng):
    feeds = _feeds(np.random.RandomState(3), 11)

    def train(streaming):
        pt.framework.unique_name.generator = \
            pt.framework.UniqueNameGenerator()
        main, startup, loss = _linreg_program()
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            if streaming:
                losses = []
                for h in exe.run_stream(main, iter(feeds),
                                        fetch_list=[loss], window=4):
                    assert h.n_steps in (4, 3)
                    losses.extend(
                        float(v) for v in np.asarray(h.result()[0]).ravel())
            else:
                losses = [float(np.asarray(
                    exe.run(main, feed=f, fetch_list=[loss])[0]).reshape(()))
                    for f in feeds]
            params = {v.name: np.array(scope.get(v.name))
                      for v in main.list_vars()
                      if isinstance(v, pt.Parameter)}
        return losses, params

    seq_losses, seq_params = train(False)
    st_losses, st_params = train(True)
    assert len(st_losses) == len(seq_losses) == 11
    np.testing.assert_allclose(st_losses, seq_losses, rtol=1e-6)
    for name in seq_params:
        np.testing.assert_allclose(st_params[name], seq_params[name],
                                   rtol=1e-5, atol=1e-7)


def test_run_stream_flushes_on_signature_change(rng):
    feeds = _feeds(np.random.RandomState(5), 5, bs=8) + \
        _feeds(np.random.RandomState(6), 2, bs=3)  # short final batches
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        sizes = [h.n_steps for h in exe.run_stream(
            main, iter(feeds), fetch_list=[loss], window=4)]
    assert sizes == [4, 1, 2]  # window, sig-change flush, tail


def test_run_stream_in_flight_cap_and_buffer_release(rng):
    """Acceptance: async fetches never hold more than the configured
    in-flight window of device buffers — asserted both via the handle
    accounting and via jax.live_arrays() (the PR 2 introspection hook):
    stacked fetch buffers from resolved windows must be gone."""
    import gc

    import jax

    feeds = _feeds(np.random.RandomState(7), 20)
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    win_size = 5  # distinctive leading dim for live-array accounting

    def stacked_live():
        # the stacked LOSS fetch buffer is the only (win_size,)-shaped
        # array in this program (feeds carry trailing dims)
        return sum(1 for a in jax.live_arrays()
                   if getattr(a, "shape", ()) == (win_size,))

    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        async_exec.reset_inflight_stats()
        handles = []
        max_stacked = 0
        for h in exe.run_stream(main, iter(feeds), fetch_list=[loss],
                                window=win_size, in_flight=2):
            handles.append(h)
            max_stacked = max(max_stacked, stacked_live())
        assert async_exec.inflight_stats()["high_water"] <= 2
        # ≤ in_flight unresolved windows at any point mid-stream; the
        # trailing ones were drained by the generator's finally
        assert all(h._resolved for h in handles)
        assert all(h.raw() is None for h in handles)
        # live stacked fetch buffers never exceeded the window cap
        # (1 fetch var per window here, +1 for the one being produced)
        assert max_stacked <= 2 + 1, max_stacked
        gc.collect()
        assert stacked_live() == 0
    # results stay readable after the device buffers are gone
    total = sum(np.asarray(h.result()[0]).ravel().size for h in handles)
    assert total == 20


def test_chained_cache_lru_bounded(rng, monkeypatch):
    from paddle_tpu.observability import telemetry

    monkeypatch.setenv("PADDLE_TPU_CHAINED_CACHE", "2")
    feeds = _feeds(np.random.RandomState(9), 1)[0]
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    ev0 = telemetry.CHAINED_EVICTIONS.value()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        for n in (2, 3, 4, 5):
            exe.run_chained(main, feed=feeds, fetch_list=[loss], n_steps=n)
        (step,) = [s for s in exe._cache.values() if s.fetch_names]
        assert len(step._chained) == 2
        # unroll="auto" resolves to unrolled windows on the CPU backend
        assert (5, False, True) in step._chained
        assert telemetry.CHAINED_EVICTIONS.value() - ev0 == 2
        # reuse refreshes recency: 5 survives another insertion
        exe.run_chained(main, feed=feeds, fetch_list=[loss], n_steps=5)
        exe.run_chained(main, feed=feeds, fetch_list=[loss], n_steps=6)
        assert (5, False, True) in step._chained
        assert (6, False, True) in step._chained


def test_run_sync_false_and_return_numpy_false(rng):
    """Satellite: return_numpy=False hands back the device arrays
    untouched; sync=False wraps them in a lazy FetchHandle."""
    import jax

    feeds = _feeds(np.random.RandomState(11), 1)[0]
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        dev = exe.run(main, feed=feeds, fetch_list=[loss],
                      return_numpy=False)
        assert isinstance(dev[0], jax.Array)
        h = exe.run(main, feed=feeds, fetch_list=[loss], sync=False)
        assert isinstance(h, async_exec.FetchHandle)
        v = float(np.asarray(h.result()[0]).reshape(()))
        assert np.isfinite(v)
        ch = exe.run_chained(main, feed=feeds, fetch_list=[loss],
                             n_steps=3, return_numpy=False)
        assert isinstance(ch[0], jax.Array) and ch[0].shape[0] == 3


# ---------------------------------------------------------------------------
# Streaming trainer driver
# ---------------------------------------------------------------------------


class _DictDS:
    def __init__(self, feeds):
        self.feeds = feeds

    def _iter_batches(self):
        yield from self.feeds


def _train_params(window, feeds, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_STREAM_WINDOW", str(window))
    pt.framework.unique_name.generator = pt.framework.UniqueNameGenerator()
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(main, _DictDS(feeds), fetch_list=[loss])
        return {v.name: np.array(scope.get(v.name))
                for v in main.list_vars() if isinstance(v, pt.Parameter)}


def test_trainer_streaming_matches_per_step(monkeypatch):
    feeds = _feeds(np.random.RandomState(13), 10)
    p_seq = _train_params(1, feeds, monkeypatch)
    p_stream = _train_params(4, feeds, monkeypatch)
    assert p_seq.keys() == p_stream.keys()
    for name in p_seq:
        np.testing.assert_allclose(p_stream[name], p_seq[name],
                                   rtol=1e-5, atol=1e-7)


def test_trainer_streaming_preempts_at_window_boundary(monkeypatch):
    from paddle_tpu.observability import events

    feeds = _feeds(np.random.RandomState(17), 12)

    class _PreemptingDS:
        def _iter_batches(self):
            for i, f in enumerate(feeds):
                if i == 6:  # mid-window for window=4
                    preemption.request_stop("test")
                yield f

    monkeypatch.setenv("PADDLE_TPU_STREAM_WINDOW", "4")
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    events.clear()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.train_from_dataset(main, _PreemptingDS(), fetch_list=[loss])
    summaries = [e for e in events.recent()
                 if e["kind"] == "step_summary"
                 and e.get("site") == "train_from_dataset"]
    assert summaries and summaries[-1]["stop"] == "preempted"
    # stopped at the batch boundary where the request landed: the
    # partial second window (steps 4-5) flushed, nothing after ran
    assert summaries[-1]["steps"] == 6


def test_trainer_fault_spec_forces_per_step(monkeypatch):
    """An active fault spec must drop the window to 1 so step=N clauses
    fire exactly at step N."""
    feeds = _feeds(np.random.RandomState(19), 8)
    monkeypatch.setenv("PADDLE_TPU_STREAM_WINDOW", "4")
    monkeypatch.setenv("PADDLE_TPU_FAULT_SPEC", "step=3:error")
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        with pytest.raises(faults.FaultInjected):
            exe.train_from_dataset(main, _DictDS(feeds),
                                   fetch_list=[loss])


def test_trainer_raise_level_numerics_forces_per_step(monkeypatch):
    """PADDLE_TPU_CHECK_NUMERICS=2 must stop BEFORE the next step
    dispatches — the driver drops to window=1 so no post-NaN step
    mutates the scope before the raise."""
    from paddle_tpu.trainer import _stream_window

    monkeypatch.setenv("PADDLE_TPU_STREAM_WINDOW", "4")
    assert _stream_window() == 4
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    assert _stream_window() == 1
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "1")
    assert _stream_window() == 4  # warn level: windowed checks are fine

    feeds = _feeds(np.random.RandomState(29), 8)
    feeds[2]["x"][0, 0] = np.nan
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        with pytest.raises(health.NumericsError):
            exe.train_from_dataset(main, _DictDS(feeds),
                                   fetch_list=[loss])


def test_multitrainer_streaming_converges(monkeypatch):
    from paddle_tpu.trainer import train_from_dataset_multithread

    monkeypatch.setenv("PADDLE_TPU_STREAM_WINDOW", "3")
    rng = np.random.RandomState(23)
    W = rng.rand(13, 1)
    main, startup, loss = _linreg_program()
    exe = pt.Executor(pt.CPUPlace())

    def factory(worker_id, num_workers):
        r = np.random.RandomState(100 + worker_id)

        def gen():
            for _ in range(12):
                X = r.rand(8, 13).astype("float32")
                yield {"x": X, "y": (X @ W).astype("float32")}
        return _DictDS(list(gen()))

    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        X = rng.rand(16, 13).astype("float32")
        probe = {"x": X, "y": (X @ W).astype("float32")}
        initial = float(np.asarray(exe.run(
            main, feed=probe, fetch_list=[loss],
            scope=scope)[0]).reshape(()))
        steps = train_from_dataset_multithread(
            exe, main, factory, thread_num=2, fetch_list=[loss],
            scope=scope)
        assert steps == 24
        final = float(np.asarray(exe.run(
            main, feed=probe, fetch_list=[loss],
            scope=scope)[0]).reshape(()))
    assert final < initial * 0.5


# ---------------------------------------------------------------------------
# Async train_loop (jax-native): equivalence + preempt-mid-window resume
# ---------------------------------------------------------------------------


def _tiny_mlp_setup(n_steps=8):
    import jax
    import jax.numpy as jnp
    import optax

    from paddle_tpu.models.common import ParamStore, dense
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.train import make_train_step

    def make_params():
        s = ParamStore(jax.random.key(0))
        s.dense("fc", 8, 4)
        return s.params, s.axes

    _, axes = make_params()
    mesh = make_mesh()

    def loss_fn(params, batch, rng):
        out = dense(params, "fc", batch["x"]).astype(jnp.float32)
        return jnp.mean((out - batch["y"]) ** 2)

    init_state, step_fn = make_train_step(
        loss_fn, optax.adam(1e-2), mesh, axes)

    def batch_fn(step):
        if step >= n_steps:
            return None
        k = jax.random.fold_in(jax.random.key(99), step)
        return {"x": jax.random.normal(k, (8, 8), "float32"),
                "y": jax.random.normal(jax.random.fold_in(k, 1), (8, 4),
                                       "float32")}

    return make_params, init_state, step_fn, batch_fn


def test_train_loop_async_fetch_matches_sync():
    import jax

    from paddle_tpu.parallel.train import train_loop

    make_params, init_state, step_fn, batch_fn = _tiny_mlp_setup()
    rng = jax.random.key(7)
    _, sync_losses, _ = train_loop(
        step_fn, init_state(make_params()[0]), batch_fn, rng=rng,
        fetch_window=1)
    async_exec.reset_inflight_stats()
    _, async_losses, _ = train_loop(
        step_fn, init_state(make_params()[0]), batch_fn, rng=rng,
        fetch_window=3)
    # bit-identical: same dispatches, only the fetch timing moved
    assert async_losses == sync_losses
    assert async_exec.inflight_stats()["high_water"] <= 3


def test_train_loop_preempt_mid_window_resumes_identically(
        tmp_path, monkeypatch):
    """Acceptance satellite: preemption at a step boundary mid-window
    (step 5, fetch_window 3) checkpoints via the PR 4 CheckpointManager
    and the resumed run reproduces the uninterrupted loss trajectory
    bit for bit."""
    import jax

    from paddle_tpu.resilience import CheckpointManager
    from paddle_tpu.parallel.train import train_loop

    make_params, init_state, step_fn, batch_fn = _tiny_mlp_setup()
    rng = jax.random.key(7)

    base_state, base_losses, stop = train_loop(
        step_fn, init_state(make_params()[0]), batch_fn, rng=rng,
        fetch_window=3)
    assert stop == "completed" and sorted(base_losses) == list(range(8))

    mgr = CheckpointManager(str(tmp_path), retry_base_s=0.01)
    monkeypatch.setenv("PADDLE_TPU_FAULT_SPEC", "step=5:preempt")
    state, first_losses, stop = train_loop(
        step_fn, init_state(make_params()[0]), batch_fn, rng=rng,
        manager=mgr, fetch_window=3)
    assert stop == "preempted" and int(state.step) == 5
    assert sorted(first_losses) == [0, 1, 2, 3, 4]
    assert mgr.committed_steps() == [5]

    monkeypatch.delenv("PADDLE_TPU_FAULT_SPEC")
    faults.reset()
    preemption.reset()
    restored = mgr.restore_latest(init_state(make_params()[0]))
    assert int(restored.step) == 5
    state, resumed_losses, stop = train_loop(
        step_fn, restored, batch_fn, rng=rng, fetch_window=3)
    assert stop == "completed" and int(state.step) == 8
    assert sorted(resumed_losses) == [5, 6, 7]
    merged = {**first_losses, **resumed_losses}
    assert merged == base_losses


def test_train_loop_health_check_forces_sync(monkeypatch):
    """With PADDLE_TPU_CHECK_NUMERICS the per-step loss check needs the
    value immediately — async decimation must yield to correctness."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    from paddle_tpu.parallel.train import train_loop

    class _S:
        def __init__(self, step):
            self.step = step
            self.opt_state = None

    def nan_at_2(state, batch, rng):
        return _S(state.step + 1), (float("nan") if state.step == 2
                                    else 0.5)

    with pytest.raises(health.NumericsError):
        train_loop(nan_at_2, _S(0), [{} for _ in range(5)],
                   fetch_window=4)
