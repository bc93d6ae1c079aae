"""Subprocess-hygiene meta-tests (VERDICT r4 item 2).

Round 4's driver evidence was zeroed by six orphaned ps_worker.py
processes leaked through an assertion path; a chip belongs to one
process at a time, so a leaked worker poisons every later job. These tests prove the conftest
discipline actually holds: a test that spawns a child and then FAILS
must still leak zero processes, and stray worker orphans are reapable by
cmdline. Reference analogue: test_dist_base kill-and-join
(/root/reference/python/paddle/fluid/tests/unittests/test_dist_base.py:629).
"""

import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _alive(pid):
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split()[2] != "Z"
    except OSError:
        return False


def test_forced_failure_leaks_no_processes(tmp_path):
    """Run the victim test (spawns a sleeper, then asserts False) in a
    child pytest; the victim's failure must not leak its sleeper."""
    pid_file = tmp_path / "victim_child.pid"
    env = dict(os.environ, META_PID_FILE=str(pid_file))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.join("tests", "_meta_leak_victim.py")],
        cwd=os.path.dirname(HERE), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0, "victim test unexpectedly passed:\n" + \
        proc.stdout
    assert pid_file.exists(), "victim never spawned its child:\n" + \
        proc.stdout + proc.stderr
    pid = int(pid_file.read_text())
    deadline = time.time() + 15
    while _alive(pid) and time.time() < deadline:
        time.sleep(0.5)
    assert not _alive(pid), (
        f"sleeper pid {pid} survived the failing test's teardown — "
        "conftest._reap_spawned_processes is broken")


def test_reap_stray_workers_by_cmdline():
    """conftest.reap_stray_workers must SIGKILL processes whose cmdline
    names a repo worker script (the session-end orphan sweep)."""
    from conftest import reap_stray_workers

    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(300)",
         "tests/ps_worker.py"])  # marker argv, same cmdline shape as a leak
    try:
        time.sleep(0.2)
        reaped = reap_stray_workers()
        assert proc.pid in reaped, f"{proc.pid} not reaped (got {reaped})"
        proc.wait(timeout=10)
        assert proc.returncode is not None
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_tracked_popen_registers_and_reaps():
    """The global Popen patch registers instances; _kill_wait terminates a
    live one without error."""
    import conftest

    before = len(conftest._live_procs)
    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(300)"])
    assert len(conftest._live_procs) == before + 1
    assert conftest._live_procs[-1].pid == proc.pid
    conftest._kill_wait(proc)
    assert proc.poll() is not None
