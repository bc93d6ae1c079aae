"""Single-flight TPU lock tests (VERDICT r4 item 6).

One chip; a second process that initializes the backend while the
first holds it fails or hangs. The
lock serializes bench.py and every tools/ entry. These tests prove the
three load-bearing behaviors: mutual exclusion, automatic release when
a holder dies (an aborted tool run can't wedge the next bench), and
lease-expiry kill of a hung holder INCLUDING its subprocess tree.
"""

import json
import multiprocessing as mp
import os
import subprocess
import sys
import time

from paddle_tpu.core import tpu_lock


def _hold(lock_path, lease, hold_s, q):
    fd = tpu_lock.acquire(timeout=10, lease_s=lease, lock_path=lock_path)
    q.put(os.getpid())
    time.sleep(hold_s)
    tpu_lock.release(fd)


def test_mutual_exclusion(tmp_path):
    path = str(tmp_path / "lock")
    q = mp.Queue()
    proc = mp.Process(target=_hold, args=(path, 60, 3, q))
    proc.start()
    q.get(timeout=10)
    t0 = time.time()
    with tpu_lock.tpu_singleflight(timeout=30, lock_path=path):
        waited = time.time() - t0
    proc.join(timeout=10)
    assert 2 < waited < 15, f"should have waited for the 3s holder: {waited}"


def test_aborted_holder_releases_immediately(tmp_path):
    """SIGKILLed holder (aborted tool run) => flock released by the kernel;
    the next acquire must succeed without waiting for any lease."""
    path = str(tmp_path / "lock")
    q = mp.Queue()
    proc = mp.Process(target=_hold, args=(path, 3600, 300, q))
    proc.start()
    q.get(timeout=10)
    proc.kill()  # abort mid-hold, no release() runs
    proc.join(timeout=10)
    t0 = time.time()
    with tpu_lock.tpu_singleflight(timeout=30, lock_path=path):
        waited = time.time() - t0
    assert waited < 10, f"lock not auto-released by holder death: {waited}"


def test_reap_spares_registered_waiters(tmp_path):
    """ADVICE r5: _reap_tpu_orphans must not SIGKILL a marker-matching
    process that is merely BLOCKED IN acquire() on the same lock. A
    holder dies with a waiter queued; the next acquirer's orphan sweep
    runs (dead previous holder) and must spare the registered waiter,
    which then gets the lock in turn."""
    path = str(tmp_path / "lock")
    # the waiter runs a script NAMED bench.py so its argv matches the
    # orphan markers — the exact false-positive shape from the advisory
    waiter_script = tmp_path / "bench.py"
    waiter_script.write_text(f"""
import json, os, sys, time
sys.path.insert(0, {json.dumps(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))})
from paddle_tpu.core import tpu_lock
fd = tpu_lock.acquire(timeout=60, lock_path={json.dumps(path)})
print("ACQUIRED", flush=True)
tpu_lock.release(fd)
""")
    q = mp.Queue()
    holder = mp.Process(target=_hold, args=(path, 3600, 300, q))
    holder.start()
    q.get(timeout=10)
    waiter = subprocess.Popen(
        [sys.executable, str(waiter_script)], stdout=subprocess.PIPE,
        text=True)
    try:
        deadline = time.time() + 15
        waiters_dir = tmp_path / "lock.waiters"
        while time.time() < deadline:
            if waiters_dir.is_dir() and any(
                    n.isdigit() for n in os.listdir(waiters_dir)):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("waiter never registered its beacon")
        holder.kill()  # dead previous holder => next acquirer sweeps
        holder.join(timeout=10)
        # contend: we or the waiter wins first; either way the sweep
        # that runs on OUR acquire must leave the waiter alive
        fd = tpu_lock.acquire(timeout=30, lock_path=path)
        assert waiter.poll() is None or waiter.returncode == 0, \
            f"registered waiter was reaped (rc={waiter.returncode})"
        tpu_lock.release(fd)
        out, _ = waiter.communicate(timeout=30)
        assert waiter.returncode == 0 and "ACQUIRED" in out, \
            f"waiter rc={waiter.returncode} out={out!r}"
    finally:
        if waiter.poll() is None:
            waiter.kill()
        if holder.is_alive():
            holder.kill()


def test_expired_lease_holder_and_children_killed(tmp_path):
    """A holder alive past its lease is SIGKILLed together with its
    descendant subprocesses (bench children drive the chip; killing only
    the parent would orphan them mid-compile)."""
    path = str(tmp_path / "lock")
    pid_file = tmp_path / "pids.json"
    script = f"""
import json, os, subprocess, sys, time
sys.path.insert(0, {json.dumps(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))})
from paddle_tpu.core import tpu_lock
fd = tpu_lock.acquire(timeout=10, lease_s=1.0, lock_path={json.dumps(path)})
child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(300)"])
json.dump({{"holder": os.getpid(), "child": child.pid}},
          open({json.dumps(str(pid_file))}, "w"))
time.sleep(300)
"""
    proc = subprocess.Popen([sys.executable, "-c", script])
    deadline = time.time() + 20
    while not pid_file.exists() and time.time() < deadline:
        time.sleep(0.2)
    pids = json.loads(pid_file.read_text())
    time.sleep(1.2)  # let the 1s lease expire
    t0 = time.time()
    with tpu_lock.tpu_singleflight(timeout=30, lock_path=path):
        waited = time.time() - t0
    assert waited < 15, f"expired holder not killed in time: {waited}"
    proc.wait(timeout=10)
    assert proc.returncode == -9, f"holder not SIGKILLed: {proc.returncode}"
    for _ in range(50):
        if not os.path.exists(f"/proc/{pids['child']}"):
            break
        with open(f"/proc/{pids['child']}/stat") as f:
            if f.read().split()[2] == "Z":
                break
        time.sleep(0.2)
    else:
        raise AssertionError(
            f"holder's child {pids['child']} survived the lease-expiry kill")
