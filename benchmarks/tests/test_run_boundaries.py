"""Where a run begins and ends (``harness/boundaries.py``), on the CPU with
no JAX backend: the zombie leader the old ``_pid_gone`` called gone, an
orphaned grandchild, a descendant that ignores SIGTERM, the start gate on a
temporary file, and the failure line. The children are small scripts run by
this interpreter; every wait has a deadline of its own.

Each test that reaps runs in a child of its own (``_in_child``): ``reap``
waits for *every* child of its process, and pytest's process has others.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from benchmarks.harness import boundaries

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the main thread ends (``pthread_exit``) while a second thread lives on:
# ``/proc/<pid>/stat`` reads Z and the group cannot be reaped until it ends
ZOMBIE_LEADER = """
import ctypes, sys, threading, time
held = open(sys.argv[2]) if len(sys.argv) > 2 else None
threading.Thread(target=time.sleep, args=(float(sys.argv[1]),)).start()
ctypes.CDLL(None).pthread_exit(None)
"""
# a child that starts a grandchild and exits at once: the grandchild is an orphan
ORPHANER = """
import subprocess, sys
subprocess.Popen([sys.executable, "-c", "import time; time.sleep(%s)" % sys.argv[1]])
"""
DEAF = """
import signal, time
signal.signal(signal.SIGTERM, signal.SIG_IGN)
print("deaf", flush=True)
time.sleep(60)
"""
OPENER = """
import sys, time
f = open(sys.argv[1]); print("holding", flush=True); time.sleep(float(sys.argv[2]))
"""
# ``mmap.mmap`` keeps a duplicate of the descriptor: close it, as a driver's
# library that maps a device's registers and closes the file would
MAPPER = """
import ctypes, mmap, os, sys, time
fd = os.open(sys.argv[1], os.O_RDWR)
libc = ctypes.CDLL(None)
libc.mmap.restype = ctypes.c_void_p
libc.mmap.argtypes = [ctypes.c_void_p, ctypes.c_size_t] + [ctypes.c_int] * 3 + [ctypes.c_long]
assert libc.mmap(None, 4096, mmap.PROT_READ, mmap.MAP_SHARED, fd, 0) not in (None, 2**64 - 1)
os.close(fd)
print("holding", flush=True); time.sleep(float(sys.argv[2]))
"""


def _in_child(body: str, timeout: float = 30.0) -> dict:
    """Run ``body`` in a fresh interpreter that is its own subreaper; the
    body prints one JSON object last."""
    script = (
        "import json, os, signal, subprocess, sys, time\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmarks.harness import boundaries\n"
        f"ZOMBIE_LEADER, ORPHANER, DEAF = {ZOMBIE_LEADER!r}, {ORPHANER!r}, {DEAF!r}\n"
        "boundaries.become_subreaper()\n" + textwrap.dedent(body))
    done = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=timeout)
    assert done.returncode == 0, done.stderr
    return json.loads(done.stdout.splitlines()[-1])


def _state(pid: int) -> str:
    return boundaries.process(pid)["state"]


def test_a_zombie_leader_with_a_live_thread_is_not_gone():
    got = _in_child("""
        child = subprocess.Popen([sys.executable, "-c", ZOMBIE_LEADER, "2.0"])
        deadline = time.time() + 10
        state = None
        while state != "Z" and time.time() < deadline:
            time.sleep(0.02)
            state = boundaries.process(child.pid)["state"]
        threads = boundaries.process(child.pid)["threads"]
        unreaped = os.waitpid(child.pid, os.WNOHANG)
        t0 = time.time()
        end = boundaries.reap(deadline_s=20)
        print(json.dumps({"state": state, "threads": threads, "unreaped": list(unreaped),
                          "end": end, "pid": child.pid, "took": time.time() - t0,
                          "in_proc": os.path.exists(f"/proc/{child.pid}")}))
    """)
    # what the old test called gone: the leader's line reads Z ...
    assert got["state"] == "Z" and got["threads"] >= 1 and got["unreaped"] == [0, 0]
    # ... and the run ends only when the group's last thread has
    assert 1.5 <= got["end"]["teardown_s"] <= 10 and got["took"] >= 1.5
    assert got["end"]["ended"] == 1 and got["end"]["slowest"]["pid"] == got["pid"]
    assert got["end"]["slowest"]["seconds"] >= 1.5
    assert not got["in_proc"]


def test_an_orphaned_grandchild_is_waited_for():
    got = _in_child("""
        child = subprocess.Popen([sys.executable, "-c", ORPHANER, "1.5"])
        child.wait(timeout=10)
        left = boundaries.descendants()
        end = boundaries.reap(deadline_s=20)
        print(json.dumps({"left": list(left.values()), "end": end,
                          "after": len(boundaries.descendants()), "me": os.getpid()}))
    """)
    (orphan,) = got["left"]
    assert orphan["ppid"] == got["me"]  # it came to the subreaper, not to init
    assert got["end"]["ended"] == 1 and got["end"]["slowest"]["pid"] == orphan["pid"]
    assert 1.0 <= got["end"]["teardown_s"] <= 10
    assert "time.sleep" in got["end"]["slowest"]["command"]
    assert got["after"] == 0


def test_a_descendant_deaf_to_sigterm_is_named_killed_and_reaped():
    got = _in_child("""
        child = subprocess.Popen([sys.executable, "-c", DEAF], stdout=subprocess.PIPE)
        child.stdout.readline()
        child.terminate()  # as WorkerPool.shutdown does, and does not wait
        try:
            boundaries.reap(deadline_s=1.0)
            out = {"raised": False}
        except boundaries.RunVoid as void:
            out = {"raised": True, "message": str(void), "details": void.details,
                   "code_is_message": void.code == str(void)}
        out.update(pid=child.pid, in_proc=os.path.exists(f"/proc/{child.pid}"),
                   after=len(boundaries.descendants()))
        print(json.dumps(out))
    """)
    assert got["raised"] and got["code_is_message"]  # a message as the code exits 1
    (left,) = got["details"]["leftovers"]
    assert left["pid"] == got["pid"] and left["state"] in "SR" and left["threads"] == 1
    assert "SIG_IGN" in left["command"] and f"pid {got['pid']}" in got["message"]
    assert got["details"]["teardown_s"] >= 1.0
    assert not got["in_proc"] and got["after"] == 0


@pytest.mark.parametrize("holder,how", [(OPENER, "open"), (MAPPER, "mapped")],
                         ids=["open", "mapped"])
def test_the_gate_waits_while_another_process_holds_the_file(tmp_path, holder, how):
    chip = tmp_path / "accel0"
    chip.write_bytes(b"\0" * 4096)
    assert boundaries.wait_chips_free([str(chip)], deadline_s=5)["chips_wait_s"] == 0.0
    child = subprocess.Popen([sys.executable, "-c", holder, str(chip), "1.5"],
                             stdout=subprocess.PIPE)
    try:
        child.stdout.readline()
        t0 = time.monotonic()
        got = boundaries.wait_chips_free([str(chip)], deadline_s=20, poll_s=0.05)
        took = time.monotonic() - t0
    finally:
        child.kill()
        child.wait(timeout=10)
    assert 1.0 <= got["chips_wait_s"] <= took <= 10
    (held,) = got["holders"]
    assert held["pid"] == child.pid and held["holds"] == [f"{os.path.realpath(chip)} ({how})"]
    assert held["command"].startswith(sys.executable)


def test_the_gate_sees_a_file_held_by_the_thread_of_a_zombie_leader(tmp_path):
    """The case the gate is for: the last run's worker, leader ``Z``, its
    threads still closing the device files."""
    chip = tmp_path / "accel0"
    chip.write_bytes(b"\0")
    child = subprocess.Popen([sys.executable, "-c", ZOMBIE_LEADER, "1.5", str(chip)])
    try:
        deadline = time.time() + 10
        while _state(child.pid) != "Z" and time.time() < deadline:
            time.sleep(0.02)
        assert _state(child.pid) == "Z"
        assert os.listdir(f"/proc/{child.pid}/fd") == []  # the leader's own shows nothing
        got = boundaries.wait_chips_free([str(chip)], deadline_s=20, poll_s=0.05)
    finally:
        child.wait(timeout=10)
    assert got["chips_wait_s"] >= 0.5
    assert [h["pid"] for h in got["holders"]] == [child.pid]
    assert got["holders"][0]["state"] == "Z"


def test_past_its_deadline_the_gate_names_the_holder_and_kills_nothing(tmp_path):
    chip = tmp_path / "accel0"
    chip.write_bytes(b"\0")
    child = subprocess.Popen([sys.executable, "-c", OPENER, str(chip), "30"],
                             stdout=subprocess.PIPE)
    try:
        child.stdout.readline()
        with pytest.raises(boundaries.RunVoid) as void:
            boundaries.wait_chips_free([str(chip)], deadline_s=0.5, poll_s=0.05)
        assert child.poll() is None  # the gate only waits
    finally:
        child.kill()
        child.wait(timeout=10)
    assert void.value.details["holders"][0]["pid"] == child.pid
    assert f"pid {child.pid}" in str(void.value) and "Nothing was started" in str(void.value)
    assert void.value.details["chips_wait_s"] >= 0.5


def test_the_gate_waits_while_a_device_file_refuses_to_open(monkeypatch):
    """What the chip showed (PR 47): a dying worker's last thread holds the
    VFIO group for seconds while ``/proc`` shows nobody with it open. The
    gate asks the file itself, as the run's own worker is about to."""
    import errno

    asked = []
    real_open = os.open

    def vfio_open(path, flags, *rest):
        if path != "/dev/vfio/9":
            return real_open(path, flags, *rest)
        asked.append(flags)
        if len(asked) <= 3:
            raise OSError(errno.EBUSY, "Device or resource busy")
        return real_open(os.devnull, os.O_RDONLY)

    monkeypatch.setattr(boundaries.os, "open", vfio_open)
    got = boundaries.wait_chips_free(["/dev/vfio/9"], deadline_s=20, poll_s=0.1)
    assert len(asked) == 4 and got["refused"] == ["/dev/vfio/9"]
    assert 0.25 <= got["chips_wait_s"] <= 5
    assert [h for h in got["holders"] if not h.get("dying")] == []
    asked.clear()
    monkeypatch.setattr(boundaries, "refused", lambda paths: list(paths))
    with pytest.raises(boundaries.RunVoid) as void:
        boundaries.wait_chips_free(["/dev/vfio/9"], deadline_s=0.3, poll_s=0.05)
    assert void.value.details["refused"] == ["/dev/vfio/9"]
    assert "refused to open: ['/dev/vfio/9']" in str(void.value)
    # a file that is no VFIO group is never opened to ask
    monkeypatch.undo()
    assert boundaries.refused(["/dev/accel0", "/nonexistent"]) == []


def _main_in_child(tmp_path, body: str, timeout: float = 60.0):
    """``cli.main_then_leave`` over a toy cell whose driver is ``body``."""
    script = tmp_path / "drive.py"
    script.write_text(textwrap.dedent(f"""
        import sys, time, types
        sys.path.insert(0, {ROOT!r})
        from benchmarks.harness import boundaries, cli, manifest
        cell = {{"name": "toy", "chips": 1, "config_file": {{}},
                "traffic_file": {{"kind": "toy_driver"}}}}
        manifest.cell = lambda name: cell
        manifest.BENCH_DIR = {str(tmp_path)!r}
        cli.require_chips = lambda chips: None
        driver = types.ModuleType("benchmarks.drivers.toy_driver")
        sys.modules[driver.__name__] = driver
    """) + textwrap.dedent(body) + textwrap.dedent("""
        driver.run = run
        sys.argv = ["run.py", "--workload", "toy", "--seconds", "1"]
        cli.main_then_leave(time.time())
    """))
    done = subprocess.run([sys.executable, str(script)], capture_output=True, text=True,
                          timeout=timeout, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    return done, lines


def test_a_driver_that_raises_leaves_a_failure_line_last(tmp_path):
    done, lines = _main_in_child(tmp_path, """
        def run(run):
            import subprocess
            subprocess.Popen([sys.executable, "-c", "import time; time.sleep(1)"])
            run.phase = "check"
            raise RuntimeError("the reference disagrees")
    """)
    assert done.returncode == 1 and "the reference disagrees" in done.stderr
    assert json.loads(done.stdout.splitlines()[-1]) == lines[-1]
    last = lines[-1]
    assert last["error"] == "RuntimeError: the reference disagrees"
    assert last["phase"] == "check" and "metrics" not in last
    # what the driver left running was waited for all the same
    assert last["ended"] == 1 and last["teardown_s"] >= 0.5
    assert [l for l in lines if "chips_wait_s" in l][0]["chips_wait_s"] == 0.0
    assert lines[-2] == {"threads_alive_at_exit": []}


def test_a_thread_the_program_left_alive_is_named_and_does_not_hold_the_exit(tmp_path):
    t0 = time.monotonic()
    done, lines = _main_in_child(tmp_path, """
        def run(run):
            import threading
            threading.Thread(target=time.sleep, args=(60,), name="raylet-left").start()
            run.setup_done(time.time())
            run.reap([])
            raise SystemExit("benchmark: toy could not measure anything")
    """)
    assert done.returncode == 1 and time.monotonic() - t0 < 30
    assert lines[-2] == {"threads_alive_at_exit": ["raylet-left"]}
    assert lines[-1]["phase"] == "window" and "could not measure" in lines[-1]["error"]
    assert done.stderr.strip().endswith("benchmark: toy could not measure anything")
