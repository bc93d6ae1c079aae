"""Single-flight discipline for one TPU chip shared by several sessions
on one machine.

A chip belongs to one process at a time: a second process that
initializes the backend while the first holds it fails or hangs.
``bench.py`` and the TPU tools under ``tools/`` funnel through
:func:`tpu_singleflight` so that they queue instead. (``chip_smoke.py``
does not take the lock: it is one process on a machine of its own.
Whether this module is still needed at all is ROADMAP D8's call.)

Reference analogue: the reference serializes device-exclusive tests by
partitioning ``CUDA_VISIBLE_DEVICES`` per test process
(/root/reference/paddle/fluid/tests/unittests/CMakeLists.txt:13); with
a single chip we serialize with an fcntl lease lock instead.

Design notes:

- The lock file is MACHINE-global (default under ``tempfile.
  gettempdir()``): the chip is a machine-scoped resource, and two
  checkouts of this repo must still serialize against each other.
- ``flock`` is process-scoped, so a holder that exits (even SIGKILL)
  releases the lock automatically. Because the holder's TPU work may
  live in child subprocesses (bench.py's ``--one`` children), a fresh
  acquirer also sweeps for known orphaned TPU processes by cmdline
  before proceeding.
- Lease + auto-renew: the holder records ``{pid, argv0, acquired_at,
  lease_s}`` and :func:`tpu_singleflight` renews it from a daemon
  thread, so lease expiry means the holder is genuinely wedged (a hung
  process stops renewing; a merely slow one keeps its lease). A waiter
  that finds the lease expired SIGKILLs the holder's descendant tree,
  then the holder — an aborted or hung tool can never wedge the next
  run.
- Waiter registration: every ``acquire()`` caller drops a pid beacon in
  ``<lock>.waiters/`` for the duration of its wait, and the orphan
  sweep spares registered waiters (and their descendants). Without
  this, a second legitimate bench.py blocked in ``acquire()`` matched
  the cmdline markers and was SIGKILLed whenever a holder died with
  two or more contenders queued (ADVICE r5) — exactly the concurrency
  the lock exists to serialize.
"""

from __future__ import annotations

import contextlib
import errno
import fcntl
import glob
import json
import os
import signal
import sys
import tempfile
import threading
import time

DEFAULT_LOCK_PATH = os.environ.get(
    "PADDLE_TPU_LOCK_FILE",
    os.path.join(tempfile.gettempdir(), "paddle_tpu_singleflight.lock"))

# With auto-renew (tpu_singleflight), expiry == the holder stopped
# renewing, so the lease only needs to outlast one renew interval plus
# slack — but keep it larger than the slowest single blocking phase
# that could starve the renew thread (a first large compile, ~40 s).
DEFAULT_LEASE_S = 900.0

# Cmdline markers of processes that drive the chip; used to reap
# orphans whose lock-holding parent died (children reparent to init and
# would otherwise keep the chip busy while a new holder inits).
_TPU_PROC_MARKERS = ("bench.py", "tools/attn_ab.py", "tools/infer_bench.py",
                     "tools/op_bench.py", "tools/rn50_exp.py",
                     "tools/rn50_roofline.py", "tools/warmstart.py")


def _read_holder(path):
    try:
        with open(path, "r") as f:
            return json.loads(f.read() or "{}")
    except (OSError, ValueError):
        return {}


def _write_holder(fd, lease_s):
    os.ftruncate(fd, 0)
    os.lseek(fd, 0, os.SEEK_SET)
    os.write(fd, json.dumps({
        "pid": os.getpid(), "argv0": sys.argv[0] if sys.argv else "",
        "acquired_at": time.time(), "lease_s": lease_s,
    }).encode())
    os.fsync(fd)


def _cmdline(pid):
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return [a.decode(errors="replace")
                    for a in f.read().split(b"\0") if a]
    except OSError:
        return []


def _pid_is_python(pid):
    """True iff pid is alive AND looks like a python process (guards the
    lease-expiry kill against pid recycling)."""
    argv = _cmdline(pid)
    return bool(argv) and "python" in os.path.basename(argv[0])


def _children_map():
    """ppid -> [child pids] for every live process (one /proc walk)."""
    children = {}
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                parts = f.read().rsplit(")", 1)[1].split()
            pid = int(stat.split("/")[2])
            children.setdefault(int(parts[1]), []).append(pid)  # ppid
        except (OSError, ValueError, IndexError):
            continue
    return children


def _descendants_from(children, root_pid):
    """Breadth-first descendants of root_pid over a _children_map()."""
    out, queue = [], list(children.get(root_pid, []))
    while queue:
        pid = queue.pop(0)
        out.append(pid)
        queue.extend(children.get(pid, []))
    return out


def _descendants(root_pid):
    """All live descendant pids of root_pid (breadth-first), via /proc."""
    return _descendants_from(_children_map(), root_pid)


def _kill_tree(root_pid):
    """SIGKILL root_pid's descendants (so orphans can't outlive it), then
    root_pid itself. Returns True if anything was signalled."""
    killed = False
    for pid in _descendants(root_pid) + [root_pid]:
        try:
            os.kill(pid, signal.SIGKILL)
            killed = True
        except OSError:
            pass
    return killed


def _maybe_kill_expired_holder(path):
    info = _read_holder(path)
    pid = info.get("pid")
    if not pid or pid == os.getpid():
        return False
    expiry = info.get("acquired_at", 0) + info.get("lease_s",
                                                  DEFAULT_LEASE_S)
    if time.time() <= expiry or not _pid_is_python(pid):
        return False
    if _kill_tree(pid):
        # flock releases when the holder's fd closes at process death;
        # give the kernel a beat to reap.
        time.sleep(0.5)
        return True
    return False


def _waiters_dir(path):
    return path + ".waiters"


def _register_waiter(path):
    """Record this pid as a live waiter blocked in acquire(): the
    orphan sweep must never SIGKILL a process that is merely queueing
    for the lock (the ADVICE r5 bug — a second legitimate bench.py
    waiter matched the cmdline markers and died whenever a holder
    crashed with >=2 waiters). One beacon file per pid, removed on
    every acquire() exit path."""
    d = _waiters_dir(path)
    beacon = os.path.join(d, str(os.getpid()))
    try:
        os.makedirs(d, exist_ok=True)
        # a torn/lost beacon only widens the conservative keep-set
        # check below, so this single write needs no atomic publish
        with open(beacon, "w") as f:  # atomic-exempt: pid beacon
            f.write(json.dumps({"pid": os.getpid(),
                                "registered_at": time.time()}))
    except OSError:
        return None  # unregisterable waiter: sweep falls back to markers
    return beacon


def _unregister_waiter(beacon):
    if beacon:
        try:
            os.unlink(beacon)
        except OSError:
            pass


def _pid_start_time(pid):
    """Epoch seconds the process started: /proc/<pid>/stat field 22
    (clock ticks since boot) + boot time. None when unreadable."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            stat = f.read()
        # split after the parenthesized comm — it may contain spaces
        ticks = float(stat.rsplit(") ", 1)[1].split()[19])
        with open("/proc/stat") as f:
            for line in f:
                if line.startswith("btime "):
                    return (float(line.split()[1])
                            + ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        pass
    return None


def _live_waiter_pids(path):
    """Pids with a live waiter beacon. Beacons of dead pids are stale
    (a SIGKILLed waiter can't clean up) and are swept here — as are
    beacons whose pid was RECYCLED by an unrelated process (the process
    started after the beacon was written), which would otherwise shield
    a true orphan from the sweep forever."""
    d = _waiters_dir(path)
    try:
        names = os.listdir(d)
    except OSError:
        return set()
    live = set()
    for name in names:
        try:
            pid = int(name)
        except ValueError:
            continue
        beacon = os.path.join(d, name)
        stale = not os.path.exists(f"/proc/{pid}")
        if not stale:
            try:
                with open(beacon) as f:
                    registered_at = json.loads(f.read()).get(
                        "registered_at")
            except (OSError, ValueError):
                registered_at = None  # torn write: keep conservatively
            if registered_at is not None:
                started = _pid_start_time(pid)
                # 2 s slack covers clock-granularity skew between
                # btime-derived start and time.time() at registration
                stale = (started is not None
                         and started > registered_at + 2.0)
        if stale:
            try:
                os.unlink(beacon)
            except OSError:
                pass
        else:
            live.add(pid)
    return live


def _reap_tpu_orphans(lock_path=None):
    """Kill leftover chip-driving processes whose lock-holding ancestor
    died (e.g. bench.py's ``--one`` children after the orchestrator was
    OOM-killed: the flock released instantly, but the child is still
    mid-compile on the chip). Matched conservatively: python
    interpreters whose argv names one of the known TPU scripts, and that
    are not us, our ancestors, our descendants, or a REGISTERED WAITER
    blocked in acquire() on this lock (waiters queue legitimately; only
    true orphans — marker processes nobody is waiting behind — die)."""
    keep = {os.getpid()}
    pid = os.getpid()
    while pid > 1:  # ancestors
        try:
            with open(f"/proc/{pid}/stat") as f:
                pid = int(f.read().rsplit(")", 1)[1].split()[1])
            keep.add(pid)
        except (OSError, ValueError, IndexError):
            break
    keep.update(_descendants(os.getpid()))
    if lock_path:
        for waiter in _live_waiter_pids(lock_path):
            keep.add(waiter)
            keep.update(_descendants(waiter))
    reaped = []
    for proc_dir in glob.glob("/proc/[0-9]*"):
        pid = int(proc_dir.rsplit("/", 1)[1])
        if pid in keep:
            continue
        argv = _cmdline(pid)
        if not argv or "python" not in os.path.basename(argv[0]):
            continue
        if any(any(a.endswith(m) for m in _TPU_PROC_MARKERS)
               for a in argv[1:]):
            if lock_path:
                # re-read the beacon dir at the last moment: a waiter
                # that registered AFTER the keep-set snapshot (entered
                # acquire() while this sweep walked /proc) must not be
                # killed — the registration race is exactly the ADVICE
                # r5 false positive this sweep must never reproduce
                fresh = _live_waiter_pids(lock_path)
                shield = set(fresh)
                if fresh:  # one /proc walk covers every waiter
                    fresh_children = _children_map()
                    for w in fresh:
                        shield.update(
                            _descendants_from(fresh_children, w))
                if pid in shield:
                    keep.add(pid)
                    continue
            try:
                os.kill(pid, signal.SIGKILL)
                reaped.append(pid)
            except OSError:
                pass
    return reaped


def acquire(timeout=600.0, lease_s=DEFAULT_LEASE_S, lock_path=None,
            poll_s=2.0):
    """Block until the TPU lock is ours; return the open lock fd.

    Raises TimeoutError after ``timeout`` seconds. While waiting, a
    holder whose lease expired (== it stopped renewing: wedged) is
    SIGKILLed along with its process tree. After acquiring, known TPU
    orphans of a dead previous holder are reaped before returning.
    """
    path = lock_path or DEFAULT_LOCK_PATH
    deadline = time.monotonic() + timeout
    # registered BEFORE the first flock attempt: another contender that
    # wins the lock and runs the orphan sweep must see us as a waiter,
    # not a reapable marker-matching orphan
    beacon = _register_waiter(path)
    try:
        fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
    except OSError:
        _unregister_waiter(beacon)
        raise
    try:
        while True:
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                break
            except OSError as e:
                if e.errno not in (errno.EAGAIN, errno.EACCES):
                    os.close(fd)
                    raise
            _maybe_kill_expired_holder(path)
            if time.monotonic() >= deadline:
                holder = _read_holder(path)
                os.close(fd)
                raise TimeoutError(
                    f"TPU single-flight lock busy after {timeout:.0f}s "
                    f"(holder: {holder})")
            time.sleep(poll_s)
        prev = _read_holder(path)
        if prev.get("pid") and prev["pid"] != os.getpid() \
                and not os.path.exists(f"/proc/{prev['pid']}"):
            _reap_tpu_orphans(path)
        _write_holder(fd, lease_s)
        return fd
    finally:
        # holder or not, we are no longer *waiting*; the holder's own
        # liveness is covered by the flock + lease, and its descendants
        # are never swept while it holds the lock (the sweep only runs
        # in a process that just ACQUIRED it)
        _unregister_waiter(beacon)


def release(fd):
    try:
        os.ftruncate(fd, 0)
        fcntl.flock(fd, fcntl.LOCK_UN)
    finally:
        os.close(fd)


def renew(fd, lease_s=DEFAULT_LEASE_S):
    """Extend the current lease (auto-called by tpu_singleflight)."""
    _write_holder(fd, lease_s)


@contextlib.contextmanager
def tpu_singleflight(timeout=600.0, lease_s=DEFAULT_LEASE_S,
                     lock_path=None):
    """Hold the single-flight TPU lock for the body, renewing the lease
    from a daemon thread every lease_s/3 — so a long-but-healthy run
    keeps its lease, while a wedged process (renew thread starved or
    dead) expires and gets reaped by the next waiter."""
    t_wait = time.monotonic()
    fd = acquire(timeout=timeout, lease_s=lease_s, lock_path=lock_path)
    t_held = time.monotonic()
    stop = threading.Event()

    def _renewer():
        while not stop.wait(lease_s / 3):
            try:
                renew(fd, lease_s)
            except OSError:
                return

    thread = threading.Thread(target=_renewer, daemon=True,
                              name="tpu-lock-renew")
    thread.start()
    try:
        yield fd
    finally:
        stop.set()
        thread.join(timeout=5)  # don't close fd under a mid-renew write
        release(fd)
        # the cross-process single-flight lease rides the same held-
        # seconds/contention table as the in-process locks (the acquire
        # poll is 2s, so a wait of >=1s means another holder was inside)
        from ..analysis import lockcheck as _lockcheck  # deferred

        if _lockcheck.level() >= 1:
            _lockcheck.note_held(
                "core.tpu_lock.singleflight",
                time.monotonic() - t_held,
                contended=(t_held - t_wait) >= 1.0)