"""Where a run begins and ends (``benchmarks/README.md`` has the section).

A run begins when no other process holds a chip (``wait_chips_free``) and
ends when nothing it started is left in ``/proc`` in any state (``reap``).
Both look and wait; neither sleeps a fixed time, and the gate kills nothing
it did not start.

Why ``reap`` and not a look at ``/proc/<pid>/stat``: that file is the
thread-group *leader's* line. On SIGTERM the leader is through first and
reads ``Z``, and stays ``Z``, unreapable, until the group's last thread has
torn down the address space and closed the device files, which for a worker
that holds four TPU runtimes takes ~20 s (ROADMAP B9(i), D20; PERF.md, PR 47).
A ``waitpid`` cannot return a pid before that, so "reaped" is exact. To be
the one that reaps a grandchild whose parent has gone, the harness makes
itself the child subreaper before it starts anything: orphans come to it and
not to init, and ``waitpid(-1)`` raising ``ChildProcessError`` then proves
that no descendant is left.
"""

from __future__ import annotations

import ctypes
import errno
import glob
import os
import signal
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

DEADLINE_S = 120.0
# what ``ray_tpu/_internal/accelerators.py`` ``count_chip_devices`` counts
CHIP_GLOBS = ("/dev/accel*", "/dev/vfio/[0-9]*")
_PR_SET_CHILD_SUBREAPER = 36
_KILLED_GRACE_S = 10.0


class RunVoid(SystemExit):
    """A run boundary was not reached in time: the run is void. ``details``
    (holders or leftovers) go into the failure line."""

    def __init__(self, message: str, **details):
        super().__init__(message)
        self.details = details


def become_subreaper() -> None:
    libc = ctypes.CDLL(None, use_errno=True)
    libc.prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
    libc.prctl.restype = ctypes.c_int
    if libc.prctl(_PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_CHILD_SUBREAPER) failed")


def _pids() -> List[int]:
    return [int(name) for name in os.listdir("/proc") if name.isdigit()]


def _read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def process(pid: int) -> Optional[dict]:
    """pid, parent, state of the group's leader, threads not yet gone and
    command; None once the pid is out of ``/proc``. All but the command from
    the ``stat`` line: of a leader that reads ``Z`` while a thread is still
    closing the device, ``/proc/<pid>/task`` cannot even be listed."""
    try:
        comm, rest = _read(f"/proc/{pid}/stat").split("(", 1)[1].rsplit(")", 1)
        command = _read(f"/proc/{pid}/cmdline").replace("\0", " ").strip()
    except (FileNotFoundError, ProcessLookupError):
        return None
    fields = rest.split()
    return {"pid": pid, "ppid": int(fields[1]), "state": fields[0],
            "threads": int(fields[17]),
            "command": (command or comm)[:160]}  # a zombie's command line reads empty


def descendants() -> Dict[int, dict]:
    """Every process under this one, by ``/proc``'s parent links."""
    found = {p["pid"]: p for p in map(process, _pids()) if p is not None}
    out: Dict[int, dict] = {}
    frontier = {os.getpid()}
    while frontier:
        frontier = {pid for pid, p in found.items()
                    if p["ppid"] in frontier and pid not in out}
        out.update((pid, found[pid]) for pid in frontier)
    return out


def _wait_any() -> Optional[int]:
    """A reaped child's pid, 0 where children are left and none has ended,
    None where no child is left."""
    try:
        return os.waitpid(-1, os.WNOHANG)[0]
    except ChildProcessError:
        return None


def reap(deadline_s: float = DEADLINE_S, poll_s: float = 0.1) -> dict:
    """Wait until every descendant of this process has ended and been
    reaped. Call it only after ``ray_tpu.shutdown()`` has returned: while
    the program runs, its own ``Popen.poll()`` must find its children's
    statuses. Past the deadline: what is left is killed and reaped, and the
    run is void (``RunVoid``, with the leftovers as they were)."""
    started = time.monotonic()
    known = descendants()  # commands, while they can still be read
    ended: Dict[int, float] = {}
    while True:
        pid = _wait_any()
        if pid:
            continue
        # not every end is seen above: the program's own threads reap too
        waited = time.monotonic() - started
        alive = descendants()
        ended.update((p, waited) for p in known if p not in alive and p not in ended)
        for p, facts in alive.items():  # orphans that have come since
            known.setdefault(p, facts)
        if pid is None:
            break
        if waited > deadline_s:
            left = [dict(p, command=known[p["pid"]]["command"]) for p in alive.values()]
            _kill_and_reap(left)
            raise RunVoid(
                f"benchmark: {len(left)} process(es) the run started were still in /proc "
                f"{deadline_s:.0f} s after shutdown() returned, killed now: "
                + "; ".join("pid {pid} [{state}, {threads} threads] {command}".format(**p)
                            for p in left),
                teardown_s=waited, leftovers=left)
        time.sleep(poll_s)
    slowest = max(ended, key=ended.get, default=None)
    return {
        "teardown_s": waited, "ended": len(ended),
        "slowest": None if slowest is None else dict(
            known[slowest], seconds=ended[slowest]),
    }


def _kill_and_reap(left: Iterable[dict]) -> None:
    for p in left:
        try:
            os.kill(p["pid"], signal.SIGKILL)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + _KILLED_GRACE_S
    while _wait_any() is not None and time.monotonic() < deadline:
        time.sleep(0.05)


def chip_files() -> List[str]:
    for pattern in CHIP_GLOBS:
        found = sorted(glob.glob(pattern))
        if found:
            return found
    return []


def _held_by(pid: int, wanted: set) -> Tuple[List[str], int]:
    """Which of ``wanted`` the process has open or mapped, and how many of
    its ``/proc`` entries could not be read. Looked for under every thread:
    a leader that has exited (``Z``) shows no file and no mapping of its
    own while its threads still hold them. Threads share one table of files
    and one address space, so the first thread that shows any is enough."""
    held, unreadable = set(), 0
    try:
        tids = os.listdir(f"/proc/{pid}/task")
    except (FileNotFoundError, ProcessLookupError):
        return [], 0
    except PermissionError:
        return [], 1
    seen_fds = seen_maps = False
    for tid in tids:
        base = f"/proc/{pid}/task/{tid}"
        try:
            if not seen_fds:
                for fd in os.listdir(f"{base}/fd"):
                    seen_fds = True
                    try:
                        target = os.readlink(f"{base}/fd/{fd}")
                    except OSError:
                        continue  # closed between the listing and the look
                    if target in wanted:
                        held.add(f"{target} (open)")
            if not seen_maps:
                for line in _read(f"{base}/maps").splitlines():
                    seen_maps = True
                    fields = line.split(None, 5)
                    if len(fields) == 6 and fields[5] in wanted:
                        held.add(f"{fields[5]} (mapped)")
        except (FileNotFoundError, ProcessLookupError):
            continue  # the thread ended meanwhile
        except PermissionError:
            unreadable += 1
    return sorted(held), unreadable


def holders(paths: Iterable[str]) -> Tuple[List[dict], int]:
    """Every process other than this one that has one of ``paths`` open or
    mapped, and the number of ``/proc`` entries skipped as unreadable."""
    wanted = {os.path.realpath(p) for p in paths}
    found, unreadable = [], 0
    for pid in _pids():
        if pid == os.getpid():
            continue
        held, skipped = _held_by(pid, wanted)
        unreadable += skipped
        if held:
            found.append(dict(process(pid) or {"pid": pid}, holds=held))
    return found, unreadable


def refused(paths: Iterable[str]) -> List[str]:
    """The VFIO group files among ``paths`` that cannot be opened because
    another process has them (a group opens for one process at a time). It
    is the test this run's own worker is about to face, and it sees what
    ``/proc`` cannot: once a dying worker's threads have let go of their
    table of files nothing there names the device, while its last close, the
    device's reset, runs for seconds (4.3 s on one chip, ~20 s on four: my
    chip runs, PR 47). Opened and closed at once; nothing is set on it."""
    busy = []
    for path in paths:
        if not path.startswith("/dev/vfio/"):
            continue
        try:
            os.close(os.open(path, os.O_RDWR))
        except OSError as exc:
            if exc.errno == errno.EBUSY:
                busy.append(path)
    return busy


def _dying() -> List[dict]:
    """Thread groups whose leader has ended while threads have not: who a
    refused device file most likely belongs to."""
    return [dict(p, holds=[], dying=True) for p in map(process, _pids())
            if p is not None and p["state"] == "Z" and p["threads"] > 1]


def wait_chips_free(paths: Optional[Iterable[str]] = None,
                    deadline_s: float = DEADLINE_S, poll_s: float = 0.25) -> dict:
    """Wait until no other process holds a chip's device file: none has it
    open or mapped by ``/proc``, and none of them refuses to open. Returns
    the seconds waited (0.0 where the first look found them free), who held
    what at the first look, the files refused then, and the unreadable
    entries skipped. Past the deadline: ``RunVoid`` with the holders named;
    nothing was started, nothing killed."""
    paths = chip_files() if paths is None else list(paths)
    started = time.monotonic()

    def look():
        found, unreadable = holders(paths)
        busy = refused(paths)
        # held, and by nobody that /proc shows holding it: name the dying
        return found or (_dying() if busy else []), busy, unreadable

    now, busy, unreadable = look()
    first, first_busy, waited = now, busy, 0.0
    while now or busy:
        waited = time.monotonic() - started
        if waited > deadline_s:
            raise RunVoid(
                f"benchmark: after {deadline_s:.0f} s the chips are still held "
                f"(refused to open: {busy}) by: "
                + ("; ".join(f"pid {h['pid']} [{h.get('state')}] {h['holds']} "
                             f"{h.get('command')}" for h in now)
                   or "no process /proc shows")
                + ". Nothing was started, no result.",
                chips_wait_s=waited, holders=now, refused=busy)
        time.sleep(poll_s)
        now, busy, unreadable = look()
        waited = time.monotonic() - started
    return {"chips_wait_s": waited, "holders": first, "refused": first_busy,
            "unreadable": unreadable}


def threads_alive(wait_s: float = 5.0) -> List[str]:
    """The non-daemon threads still alive once each has been given the rest
    of ``wait_s`` to end: what an ordinary interpreter exit would wait for."""
    deadline = time.monotonic() + wait_s
    others = [t for t in threading.enumerate()
              if not t.daemon and t not in (threading.current_thread(),
                                            threading.main_thread())]
    for t in others:
        t.join(max(deadline - time.monotonic(), 0.0))
    return [t.name for t in others if t.is_alive()]
