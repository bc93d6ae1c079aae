"""Test harness configuration.

Mirrors the reference's test ladder (SURVEY.md §4): numpy reference → CPU
execution → multi-device. Tests run on a *virtual 8-device CPU mesh* so every
sharding/collective path compiles and executes without TPU hardware
(reference analogue: localhost-subprocess "clusters" in
python/paddle/fluid/tests/unittests/test_dist_base.py:461).
"""

import os

# Must be set before jax initializes its backends.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# float64 available for finite-difference oracles (framework code still
# declares float32 explicitly everywhere it matters).
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process tests")
    config.addinivalue_line(
        "markers", "thread_leak_ok: this test intentionally leaves "
        "threads behind (exempt from the thread-leak sentinel)")


# ---------------------------------------------------------------------------
# A pin that a later cell cannot keep. tests/benchmarks/test_xing4_cell.py
# (PR 45) asserts that its cell is the LAST entry of BENCHMARK.json's
# `workloads` ("appended, nothing moved"); every cell appended after it makes
# that one line false, and a PR that adds a cell may not edit a file the
# benchmark already has. While a later cell exists that test MUST fail
# (strict: on an assertion, and it may not pass), and everything else it
# asserts runs in tests/benchmarks/test_xing4_cell_as_left.py against the
# list as PR 45 left it. The next `benchmark` PR should compare the entry
# with its own place in the list and take this hook and that file out. (It
# lives here and not in a conftest.py of tests/benchmarks: neither directory
# is a package, and a second module named `conftest` takes this one's place
# for the tests that import from it.) The same holds of `per_layer` since
# PR 57: tests/benchmarks/test_loop_metrics.py (PR 56) asserts that the LAST ten
# per-layer metrics are its ten, and a metric appended after them (the
# contract puts a new entry at the end of its list) makes that line false;
# its body runs in tests/benchmarks/test_loop_metrics_as_left.py against
# the list as PR 56 left it. And since PR 58 of
# tests/benchmarks/test_sparse_shared_entry_share.py (PR 57), which pins
# `per_layer`'s LAST entry to its one metric: its body runs in
# tests/benchmarks/test_sparse_shared_entry_share_as_left.py. And since
# PR 60 of a COUNT: tests/benchmarks/test_granite_cell.py (PR 58) asserts
# that the benchmark has 13 cells and 10 configurations, which the next cell
# makes false; its body runs in tests/benchmarks/test_granite_cell_as_left.py
# against the lists as PR 58 left them (`_PINNED_COUNT`).
# ---------------------------------------------------------------------------

# (the list of BENCHMARK.json, the name its last entry was pinned to, the
# test that pins it)
_PINNED_LAST = (
    ("workloads", "xing4_29b_a4b.ctx12k_sessions",
     "tests/benchmarks/test_xing4_cell.py::test_the_cell_is_"
     "found_with_its_readers_and_the_issues_traffic"),
    ("per_layer", "pause_device_share",
     "tests/benchmarks/test_loop_metrics.py::test_benchmark_json_lists_"
     "the_ten_under_the_decode_engine"),
    ("per_layer", "sparse_shared_entry_share",
     "tests/benchmarks/test_sparse_shared_entry_share.py::test_the_"
     "manifest_lists_it_for_the_sparse_cell_alone"))


# (the list of BENCHMARK.json, the length it was pinned to, the test)
_PINNED_COUNT = (
    ("workloads", 13,
     "tests/benchmarks/test_granite_cell.py::test_the_cell_is_found_with_"
     "its_readers"),)


def pytest_collection_modifyitems(config, items):
    import json

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for which, count, test in _PINNED_COUNT:
        if len(bench[which]) == count:
            continue
        for item in items:
            if item.nodeid.endswith(test):
                item.add_marker(pytest.mark.xfail(
                    reason=f"pins BENCHMARK.json's {which} to {count} "
                           f"entries; there are {len(bench[which])}",
                    raises=AssertionError, strict=True))
    for which, pinned, test in _PINNED_LAST:
        last = bench[which][-1]["name"]
        if last == pinned:
            continue
        for item in items:
            if item.nodeid.endswith(test):
                item.add_marker(pytest.mark.xfail(
                    reason=f"pins BENCHMARK.json's last entry of {which} "
                           f"to {pinned}; {last} was appended after it",
                    raises=AssertionError, strict=True))


# ---------------------------------------------------------------------------
# Subprocess hygiene (round-4 post-mortem: six ps_worker.py orphans leaked by
# an assertion path wedged the single TPU chip for every later job). Every
# Popen created anywhere during a test — test code, paddle_tpu launchers,
# subprocess.run internals — is registered here and kill-waited at that
# test's teardown regardless of outcome, so no assertion failure or
# communicate() timeout can strand a pserver/trainer child. Reference
# analogue: test_dist_base's unconditional kill-and-join discipline
# (/root/reference/python/paddle/fluid/tests/unittests/test_dist_base.py:629).
# ---------------------------------------------------------------------------

import subprocess as _subprocess  # noqa: E402

_live_procs = []
_OrigPopen = _subprocess.Popen


class _TrackedPopen(_OrigPopen):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        _live_procs.append(self)


_subprocess.Popen = _TrackedPopen


def _kill_wait(proc):
    try:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    except (OSError, _subprocess.TimeoutExpired):
        # TimeoutExpired: child stuck in uninterruptible sleep (D-state)
        # — nothing more we can do, but the remaining procs/streams
        # must still get their cleanup.
        pass
    for stream in (proc.stdin, proc.stdout, proc.stderr):
        try:
            if stream:
                stream.close()
        except OSError:
            pass


@pytest.fixture(autouse=True)
def _reap_spawned_processes():
    """Kill-wait every subprocess spawned during the test, pass or fail."""
    start = len(_live_procs)
    yield
    for proc in _live_procs[start:]:
        _kill_wait(proc)
    del _live_procs[start:]


# ---------------------------------------------------------------------------
# JAX's persistent compilation cache is OFF in tier-1, and stays off. The
# benchmark's rehearsals (tests/benchmarks: `harness/device.py place_cache`)
# place it at <checkout>/.jax_cache for their process and do not give the
# settings back, so every later test of the same xdist worker compiled
# against a directory that earlier RUNS had filled: an XLA:CPU executable
# loaded from it cannot be serialized again, which is how
# test_precision.py::test_compile_cache_policy_separation (it stores and
# reloads through PADDLE_TPU_COMPILE_CACHE) failed in a whole run and never
# alone; and a JAX-cache hit is a `compile_cache` hit in the telemetry it
# counts (ISSUE 37). Whatever a test sets is given back when it ends, and
# JAX's in-memory executables go with it: one that the directory returned
# stays in the jit caches, a later engine of the same shapes is handed it
# instead of compiling, `export_warmstart` serializes it, and the engine
# that adopts the artifact dies with "Function gather_bitcast_fusion not
# found" (test_decode.py::
# test_admissions_are_assembled_on_the_device_and_warmed after a runner
# test of tests/benchmarks on the same worker, once another runner test,
# of this run or an earlier one, had filled <checkout>/.jax_cache; never
# alone).
# ---------------------------------------------------------------------------

_JAX_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                      "jax_persistent_cache_min_compile_time_secs",
                      "jax_persistent_cache_min_entry_size_bytes")
# as this worker began (not as the test found them: a fixture of a wider
# scope may have moved them before a test's own fixtures run)
_JAX_CACHE_AT_START = {k: getattr(jax.config, k) for k in _JAX_CACHE_OPTIONS}


@pytest.fixture(autouse=True)
def _jax_cache_settings_given_back():
    yield
    if {k: getattr(jax.config, k)
            for k in _JAX_CACHE_OPTIONS} != _JAX_CACHE_AT_START:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)

        for k, v in _JAX_CACHE_AT_START.items():
            jax.config.update(k, v)
        _cc.reset_cache()
        jax.clear_caches()


_WORKER_SCRIPTS = ("tests/ps_worker.py", "tests/fleet_ps_worker.py",
                   "tests/dygraph_dp_worker.py", "tests/hybrid_mesh_worker.py",
                   "tests/dist_mnist_like.py")


def reap_stray_workers():
    """SIGKILL python processes (ours or reparented-to-init orphans)
    running one of this repo's worker scripts. Matched conservatively —
    python interpreter argv0 plus a worker-script argument — so an
    editor or grep whose cmdline merely mentions the path is never
    touched. Returns the pids reaped."""
    import glob
    import signal

    reaped = []
    for pid_dir in glob.glob("/proc/[0-9]*"):
        pid = int(pid_dir.rsplit("/", 1)[1])
        if pid == os.getpid():
            continue
        try:
            with open(pid_dir + "/cmdline", "rb") as f:
                argv = [a.decode(errors="replace")
                        for a in f.read().split(b"\0") if a]
        except OSError:
            continue
        if not argv or "python" not in os.path.basename(argv[0]):
            continue
        if any(any(a.endswith(w) for w in _WORKER_SCRIPTS)
               for a in argv[1:]):
            try:
                os.kill(pid, signal.SIGKILL)
                reaped.append(pid)
            except OSError:
                pass
    return reaped


def pytest_sessionfinish(session, exitstatus):
    # Belt-and-braces: anything that escaped per-test teardown (e.g. a
    # grandchild reparented to init) is reaped by cmdline at session end.
    for proc in _live_procs:
        _kill_wait(proc)
    _live_procs.clear()
    # under xdist only the controller sweeps, after every worker is done:
    # a worker that finishes early would SIGKILL the worker scripts of
    # tests still running on the others
    if not hasattr(session.config, "workerinput"):
        reap_stray_workers()
    # Concurrency-sanitizer verdict line: when this session ran under
    # PADDLE_TPU_LOCKCHECK, print the deadlock/inversion totals so a
    # wrapper (test_lockcheck's slow family run) can assert on them
    # without needing a metrics dump to have fired.
    if os.environ.get("PADDLE_TPU_LOCKCHECK", "0") not in ("", "0"):
        try:
            from paddle_tpu.analysis import lockcheck
        except ImportError:
            return
        inversions = lockcheck.observed_inversions()
        print(f"\nLOCKCHECK deadlocks={lockcheck.deadlock_count()} "
              f"inversions={len(inversions)}")
        for inv in inversions:
            print(f"LOCKCHECK-INVERSION {inv['first']} -> "
                  f"{inv['second']} x{inv['count']}")


# ---------------------------------------------------------------------------
# Thread hygiene (ISSUE 13): every Batcher/DecodeEngine/heartbeat/PS-sender
# thread a test starts must be gone when the test ends — the "thread
# hygiene" review class from PR 3/11, now an automatic gate. Non-daemon
# leaks block interpreter exit; they fail (or warn) the leaking test
# itself, with @pytest.mark.thread_leak_ok as the explicit escape.
#   PADDLE_TPU_THREADLEAK=warn (default) | error | off
# ---------------------------------------------------------------------------

import threading as _threading  # noqa: E402
import time as _time  # noqa: E402
import warnings as _warnings  # noqa: E402


def _leaked_threads(before, grace_s: float = 1.0):
    """Live non-daemon threads that were not running at test entry.
    Threads mid-exit get `grace_s` to finish (a stop() that just
    returned may leave its worker one scheduler slice from death)."""
    deadline = _time.monotonic() + grace_s
    while True:
        leaked = [t for t in _threading.enumerate()
                  if t not in before and t.is_alive() and not t.daemon
                  and t is not _threading.current_thread()]
        if not leaked or _time.monotonic() >= deadline:
            return leaked
        _time.sleep(0.05)


@pytest.fixture(autouse=True)
def _thread_leak_sentinel(request):
    mode = os.environ.get("PADDLE_TPU_THREADLEAK", "warn").lower()
    if mode in ("off", "0", ""):
        yield
        return
    if request.node.get_closest_marker("thread_leak_ok"):
        yield
        return
    before = set(_threading.enumerate())
    yield
    leaked = _leaked_threads(before)
    if not leaked:
        return
    names = ", ".join(f"{t.name} (ident={t.ident})" for t in leaked)
    msg = (f"test leaked {len(leaked)} non-daemon thread(s): {names} — "
           f"join them in the test/fixture teardown, or mark the test "
           f"@pytest.mark.thread_leak_ok")
    if mode == "error":
        pytest.fail(msg)
    _warnings.warn(msg, stacklevel=1)


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope (the reference's tests
    rely on new Program() per test; we also reset the global singletons)."""
    import paddle_tpu as pt
    from paddle_tpu.core import executor as executor_mod
    from paddle_tpu.core import framework as fw

    old_main = fw.switch_main_program(pt.Program())
    old_startup = fw.switch_startup_program(pt.Program())
    old_scope = executor_mod._global_scope
    executor_mod._global_scope = executor_mod.Scope()
    fw.unique_name.generator = fw.UniqueNameGenerator()
    yield
    fw.switch_main_program(old_main)
    fw.switch_startup_program(old_startup)
    executor_mod._global_scope = old_scope


@pytest.fixture
def rng():
    return np.random.RandomState(1234)
