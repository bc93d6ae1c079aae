"""Persistent compile cache (core/compile_cache.py) + _JitDispatch
wiring.

The contract under test: with PADDLE_TPU_COMPILE_CACHE set, an AOT
compile happens at most once per (lowered module, jax version, backend,
device kind) ACROSS PROCESSES — later warms deserialize instead of
compiling; every failure mode (corrupt entry, version mismatch,
concurrent writers, serialization refusal) degrades to a fresh compile,
never an error; and a process restart with a warm cache reports ZERO
fresh compiles through the compile-event log, which is the whole point
(ISSUE 6 / ROADMAP item 2: restart cost must be I/O, not compilation).
"""

import json
import os
import pickle
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import compile_cache
from paddle_tpu.core.executor import _JitDispatch
from paddle_tpu.observability import events, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cc_count(event, kind="step"):
    return telemetry.COMPILE_CACHE.value(kind=kind, event=event)


def _entries(d):
    return sorted(n for n in os.listdir(d) if n.endswith(".jex"))


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = tmp_path / "cc"
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", str(d))
    return str(d)


# ---------------------------------------------------------------------------
# Hit / miss / store
# ---------------------------------------------------------------------------


def test_disabled_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
    assert not compile_cache.enabled()
    f = _JitDispatch(jax.jit(lambda x: x + 1), "step")
    assert f.warm(jnp.ones((3,)))
    assert not list(tmp_path.iterdir())


def test_second_process_worth_of_warm_hits(cache_dir):
    """Two independent _JitDispatch wrappers over the same computation:
    the first misses + stores, the second hits — no second compile."""
    x = jnp.ones((5,))
    miss0, hit0, store0 = (_cc_count("miss"), _cc_count("hit"),
                           _cc_count("store"))
    f1 = _JitDispatch(jax.jit(lambda v: v * 3 + 1), "step")
    assert f1.warm(x)
    assert _cc_count("miss") == miss0 + 1
    assert _cc_count("store") == store0 + 1
    assert len(_entries(cache_dir)) == 1

    seq_before = events.recent()[-1]["seq"] if events.recent() else -1
    f2 = _JitDispatch(jax.jit(lambda v: v * 3 + 1), "step")
    assert f2.warm(x)
    assert _cc_count("hit") == hit0 + 1
    new = [e for e in events.recent() if e["seq"] > seq_before]
    assert any(e["kind"] == "compile_cache" and e["event"] == "hit"
               for e in new)
    assert not any(e["kind"] == "compile" for e in new), \
        "a cache hit must not record a fresh compile"
    np.testing.assert_allclose(np.asarray(f2(x)), np.asarray(f1(x)))


def test_distinct_computations_distinct_entries(cache_dir):
    x = jnp.ones((4,))
    _JitDispatch(jax.jit(lambda v: v + 1), "step").warm(x)
    _JitDispatch(jax.jit(lambda v: v + 2), "step").warm(x)
    _JitDispatch(jax.jit(lambda v: v + 1), "step").warm(jnp.ones((6,)))
    assert len(_entries(cache_dir)) == 3


# ---------------------------------------------------------------------------
# Fallbacks: corrupt entry, version mismatch
# ---------------------------------------------------------------------------


def test_corrupt_entry_falls_back_to_compile(cache_dir):
    x = jnp.ones((7,))
    f1 = _JitDispatch(jax.jit(lambda v: v - 1), "step")
    assert f1.warm(x)
    (name,) = _entries(cache_dir)
    path = os.path.join(cache_dir, name)
    with open(path, "wb") as fh:
        fh.write(b"not a pickle, certainly not an executable")
    corrupt0, store0 = _cc_count("corrupt"), _cc_count("store")
    f2 = _JitDispatch(jax.jit(lambda v: v - 1), "step")
    assert f2.warm(x), "corrupt entry must fall back to a fresh compile"
    assert _cc_count("corrupt") == corrupt0 + 1
    # the fresh compile re-stored a good entry over the dropped one
    assert _cc_count("store") == store0 + 1
    assert _entries(cache_dir) == [name]
    assert float(np.asarray(f2(x))[0]) == 0.0


def test_version_mismatch_falls_back(cache_dir):
    """An entry whose embedded environment meta disagrees with this
    process (stale cache dir reused across a jax upgrade) must be
    dropped and recompiled, even though its key matches."""
    x = jnp.ones((2, 2))
    f1 = _JitDispatch(jax.jit(lambda v: v @ v), "step")
    assert f1.warm(x)
    (name,) = _entries(cache_dir)
    path = os.path.join(cache_dir, name)
    with open(path, "rb") as fh:
        entry = pickle.loads(fh.read())
    entry["jax_version"] = "0.0.0-stale"
    with open(path, "wb") as fh:
        fh.write(pickle.dumps(entry))
    corrupt0 = _cc_count("corrupt")
    f2 = _JitDispatch(jax.jit(lambda v: v @ v), "step")
    assert f2.warm(x)
    assert _cc_count("corrupt") == corrupt0 + 1


def test_renamed_entry_rejected_not_served(cache_dir):
    """An entry's bytes under the WRONG filename (copied/renamed cache
    dir) must be rejected as corrupt, not served: env meta matches
    every entry on one host, so only the embedded key catches it."""
    x = jnp.ones((3,))
    f1 = _JitDispatch(jax.jit(lambda v: v * 5), "step")
    assert f1.warm(x)
    (name,) = _entries(cache_dir)
    wrong = "0" * 64 + ".jex"
    os.rename(os.path.join(cache_dir, name),
              os.path.join(cache_dir, wrong))
    corrupt0 = _cc_count("corrupt")
    assert compile_cache.load("0" * 64, "step") is None
    assert _cc_count("corrupt") == corrupt0 + 1
    assert not os.path.exists(os.path.join(cache_dir, wrong))


def test_cache_dir_expands_tilde(monkeypatch):
    """A literal '~' from docker ENV / env_file (no shell expansion)
    must become the home dir, not a cwd-relative './~' directory."""
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "~/ptc-cache-test")
    assert compile_cache.cache_dir() == \
        os.path.expanduser("~/ptc-cache-test")


def test_load_never_raises_on_unwritable_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE",
                       str(tmp_path / "no" / "such" / "dir"))
    assert compile_cache.load("deadbeef", "step") is None
    f = _JitDispatch(jax.jit(lambda v: v + 1), "step")
    assert f.warm(jnp.ones((3,)))  # store failure must not break warm


# ---------------------------------------------------------------------------
# Retention sweep
# ---------------------------------------------------------------------------


def test_retention_entry_bound_evicts_oldest(cache_dir, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE_MAX_ENTRIES", "2")
    x = jnp.ones((4,))
    for i, shift in enumerate((1, 2, 3)):
        f = _JitDispatch(jax.jit(lambda v, s=shift: v + s), "step")
        assert f.warm(x)
        # distinct mtimes so "oldest" is well-defined on coarse clocks
        for name in _entries(cache_dir):
            p = os.path.join(cache_dir, name)
            os.utime(p, (time.time() - 100 + i, time.time() - 100 + i))
    compile_cache.sweep()
    assert len(_entries(cache_dir)) == 2


def test_retention_byte_bound(cache_dir, monkeypatch):
    x = jnp.ones((4,))
    _JitDispatch(jax.jit(lambda v: v * 5), "step").warm(x)
    _JitDispatch(jax.jit(lambda v: v * 7), "step").warm(x)
    sizes = [os.path.getsize(os.path.join(cache_dir, n))
             for n in _entries(cache_dir)]
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE_MAX_BYTES",
                       str(max(sizes)))
    evict0 = _cc_count("evict", kind="cache")  # direct sweep() label
    assert compile_cache.sweep() >= 1
    assert len(_entries(cache_dir)) <= 1
    assert _cc_count("evict", kind="cache") > evict0


# ---------------------------------------------------------------------------
# Concurrent writers
# ---------------------------------------------------------------------------

_WRITER = """
import os, sys, time
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from paddle_tpu.core.executor import _JitDispatch
# loose start-line sync so both processes race the same store window
while time.time() < {t0!r}:
    time.sleep(0.005)
f = _JitDispatch(jax.jit(lambda v: v * 2 + 4), "step")
assert f.warm(jnp.ones((16, 16)))
print("OK", flush=True)
"""


def test_concurrent_writers_one_committed_entry(cache_dir):
    """Two processes compiling the same key concurrently: atomic
    publish means exactly one committed entry, no torn files, no tmp
    litter — and the entry is loadable afterwards."""
    t0 = time.time() + 1.5
    env = dict(os.environ, PADDLE_TPU_COMPILE_CACHE=cache_dir,
               JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WRITER.format(repo=REPO, t0=t0)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=180)
        assert p.returncode == 0 and "OK" in out, err[-800:]
    names = _entries(cache_dir)
    assert len(names) == 1, names
    assert not [n for n in os.listdir(cache_dir) if ".tmp." in n], \
        "atomic writer left tmp litter"
    key = names[0][:-len(".jex")]
    assert compile_cache.load(key, "step") is not None


# ---------------------------------------------------------------------------
# Per-signature AOT retry (satellite: _tried is no longer a single flag)
# ---------------------------------------------------------------------------


def test_warm_retries_after_failure_on_new_signature():
    """An AOT failure for signature A must not lock out signature B:
    the serving engine reshapes buckets, and the reshaped bucket still
    deserves its AOT executable."""
    def fn(x):
        if x.shape[0] == 2:
            raise ValueError("trace-time failure for bs=2")
        return x + 1

    f = _JitDispatch(jax.jit(fn), "infer")
    assert not f.warm(jnp.ones((2, 3)))
    assert f.warm(jnp.ones((4, 3))), \
        "signature change after AOT failure must retry"
    assert f._aot is not None


def test_call_drift_reenables_aot():
    """A dispatch whose avals drifted from the compiled signature
    re-warms at the call's OWN signature and serves it via AOT in the
    same call — instead of riding the jit fallback and staying jit
    forever at the drifted shape."""
    f = _JitDispatch(jax.jit(lambda v: v * 2), "infer")
    a, b = jnp.ones((3,)), jnp.ones((5,))
    assert f.warm(a)
    np.testing.assert_allclose(np.asarray(f(b)), 2 * np.ones((5,)))
    assert f._tried and f._aot is not None  # warmed at b's signature
    assert f.warm(b)
    np.testing.assert_allclose(np.asarray(f(b)), 2 * np.ones((5,)))


def test_alternating_signatures_compile_once_each(monkeypatch):
    """Returning to a signature this wrapper already compiled must be
    an executable swap, not a fresh XLA compile — an SPMD loop whose
    final partial batch alternates shapes every epoch would otherwise
    pay a compile per alternation (with the persistent cache DISABLED,
    the worst case)."""
    monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
    seq0 = events.recent()[-1]["seq"] if events.recent() else -1
    f = _JitDispatch(jax.jit(lambda v: v * 2), "infer")
    a, b = jnp.ones((3,)), jnp.ones((5,))
    assert f.warm(a) and f.warm(b)
    for _ in range(3):
        assert f.warm(a) and f.warm(b)  # swaps, not compiles
    compiles = [e for e in events.recent() if e["seq"] > seq0
                and e["kind"] == "compile"]
    assert len(compiles) == 2, compiles
    # alternating DISPATCHES swap executables too (drift re-warms at
    # the call's own signature) — still no fresh compiles
    for _ in range(2):
        np.testing.assert_allclose(np.asarray(f(b)), 2 * np.ones((5,)))
        np.testing.assert_allclose(np.asarray(f(a)), 2 * np.ones((3,)))
    compiles = [e for e in events.recent() if e["seq"] > seq0
                and e["kind"] == "compile"]
    assert len(compiles) == 2, compiles


def test_failed_signature_does_not_strand_remembered_aot():
    """After an AOT failure latches one signature to the jit path, a
    DISPATCH at a different, already-compiled signature must route back
    to its remembered executable — not ride plain jit forever."""
    def fn(x):
        if x.shape[0] == 2:
            raise ValueError("trace-time failure for bs=2")
        return x + 1

    f = _JitDispatch(jax.jit(fn), "infer")
    b = jnp.ones((4, 3))
    assert f.warm(b)                      # sig B compiled + remembered
    assert not f.warm(jnp.ones((2, 3)))   # sig A fails: _aot latched None
    assert f._aot is None
    np.testing.assert_allclose(np.asarray(f(b)), np.ones((4, 3)) + 1)
    assert f._aot is not None, \
        "dispatch at a remembered signature must reinstall its AOT " \
        "executable after another signature's failure"


def test_warm_same_signature_still_cached_after_failure():
    calls = []

    def fn(x):
        calls.append(1)
        raise ValueError("always fails at trace")

    f = _JitDispatch(jax.jit(fn), "infer")
    assert not f.warm(jnp.ones((2,)))
    n = len(calls)
    assert not f.warm(jnp.ones((2,)))  # same sig: no re-lower
    assert len(calls) == n


# ---------------------------------------------------------------------------
# Restart with a warm cache: zero fresh compiles through the event log
# ---------------------------------------------------------------------------

_RESTART = """
import json, os, sys
sys.path.insert(0, {repo!r})
import numpy as np
import paddle_tpu as pt
from paddle_tpu.observability import events

main, startup = pt.Program(), pt.Program()
with pt.framework.unique_name.guard(), pt.program_guard(main, startup):
    x = pt.layers.data(name="x", shape=[4], dtype="float32")
    y = pt.layers.data(name="y", shape=[1], dtype="int64")
    h = pt.layers.fc(input=x, size=8, act="relu")
    logits = pt.layers.fc(input=h, size=3)
    loss = pt.layers.mean(
        pt.layers.softmax_with_cross_entropy(logits, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)

rng = np.random.RandomState(0)
feeds = [dict(x=rng.rand(4, 4).astype("float32"),
              y=rng.randint(0, 3, (4, 1)).astype("int64"))
         for _ in range(6)]
exe = pt.Executor(pt.CPUPlace())
with pt.scope_guard(pt.Scope()):
    exe.run(startup)
    losses = []
    for h in exe.run_stream(main, iter(feeds), fetch_list=[loss],
                            window=3):
        losses.extend(float(v) for v in np.asarray(h.result()[0]).ravel())
evs = events.recent()
print(json.dumps({{
    "losses": losses,
    "compiles": sum(1 for e in evs if e["kind"] == "compile"),
    "cache_hits": sum(1 for e in evs if e["kind"] == "compile_cache"
                      and e.get("event") == "hit"),
}}), flush=True)
"""


@pytest.mark.slow
def test_run_stream_restart_warm_cache_zero_compiles(tmp_path):
    """The headline restart-storm property: a process restart with a
    warm cache performs ZERO fresh XLA compiles (compile-event log is
    empty of `compile` kinds), every executable arriving via cache
    hits, and computes bit-identical losses."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_COMPILE_CACHE=str(tmp_path / "cc"))
    script = _RESTART.format(repo=REPO)

    def run():
        p = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["compiles"] >= 2  # startup step + stream windows
    warm = run()
    assert warm["compiles"] == 0, \
        f"restart with warm cache still compiled: {warm}"
    assert warm["cache_hits"] >= cold["compiles"]
    np.testing.assert_array_equal(np.asarray(cold["losses"]),
                                  np.asarray(warm["losses"]))


# ---------------------------------------------------------------------------
# obsdump cache subcommand (CI satellite)
# ---------------------------------------------------------------------------


def test_obsdump_cache_subcommand(tmp_path, cache_dir):
    """`obsdump.py cache` renders per-kind hit/miss/bytes from a
    metrics snapshot file — the operator's restart-storm readout."""
    from paddle_tpu import observability

    x = jnp.ones((9,))
    _JitDispatch(jax.jit(lambda v: v + 9), "step").warm(x)  # miss+store
    _JitDispatch(jax.jit(lambda v: v + 9), "step").warm(x)  # hit
    snap_path = observability.default_registry().dump(str(tmp_path))
    tool = os.path.join(REPO, "tools", "obsdump.py")

    r = subprocess.run([sys.executable, tool, "cache", snap_path,
                        "--json"], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stderr
    rows = {row["kind"]: row for row in json.loads(r.stdout)}
    step = rows["step"]
    assert step["hit"] >= 1 and step["miss"] >= 1 and step["store"] >= 1
    assert step["hit_bytes"] > 0 and step["store_bytes"] > 0
    assert 0.0 < step["hit_rate"] <= 1.0

    r = subprocess.run([sys.executable, tool, "cache", snap_path],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "hit_rate" in r.stdout and "step" in r.stdout

    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    r = subprocess.run([sys.executable, tool, "cache", str(empty)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    assert "no compile-cache samples" in r.stdout
