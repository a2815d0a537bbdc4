"""How a run ends, whoever ends it: nothing it started is left.

``run.py`` puts ``child.py`` in a session of its own, so the child and
whatever it starts (the ``g++`` of a checkout's first run) are one
process group, and ends that group on every way out (``end_group``);
told to end itself, it leaves through its ``finally``
(``exit_on_signals``); killed without a word, it is missed by the child
(``die_with_parent``).  ``chipbench/README.md``, "How a run ends".
"""

from __future__ import annotations

import ctypes
import os
import signal
import threading
import time

SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)
#: between SIGTERM and SIGKILL: a chip's holder takes ~6 s to leave on
#: SIGTERM (my chip run, PR 22)
EXIT_GRACE_S = 30.0
#: ``prctl`` option of ``<linux/prctl.h>``
PR_SET_PDEATHSIG = 1
#: how often a group is looked at while it ends, and a parent's pid
#: while it lives
LOOK_S = 0.05
PARENT_LOOK_S = 0.5


def exit_on_signals() -> None:
    """SIGTERM, SIGINT and SIGHUP raise ``SystemExit(128 + signal)``
    where Python's default would end the process past every
    ``finally``."""

    def leave(signum, frame):
        raise SystemExit(128 + signum)

    for signum in SIGNALS:
        signal.signal(signum, leave)


def command_of(pid) -> str:
    with open(f"/proc/{pid}/cmdline", "rb") as f:
        command = f.read().replace(b"\0", b" ").strip()
    return command.decode("utf-8", "replace")


def stat_of(path: str) -> tuple[str, int]:
    """State and process group from a ``stat`` file of ``/proc``."""
    with open(path) as f:
        # the fields after the command, which may hold spaces
        state, _, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
    return state, int(pgrp)


def thread_states(pid) -> str:
    """The states of a process's threads, one letter each."""
    tasks = f"/proc/{pid}/task"
    return "".join(
        stat_of(f"{tasks}/{tid}/stat")[0] for tid in os.listdir(tasks)
    )


def group_members(pgid: int) -> dict[int, str]:
    """The processes of a group that still run, each with its command
    line, from ``/proc``.  A zombie has ended and only waits for its
    parent, so it is not one; but a process whose first thread alone
    is a zombie still runs: a chip's holder that is told to end reads
    so for seconds, its ports and the chip held by the threads that
    are still leaving (my chip run, PR 37)."""
    found = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            state, pgrp = stat_of(f"/proc/{pid}/stat")
            if pgrp != pgid:
                continue
            if state not in "ZX" or thread_states(pid).strip("ZX"):
                found[int(pid)] = command_of(pid)
        except (OSError, ValueError):
            continue  # it ended between the listing and the read
    return found


def end_group(pgid: int, grace_s: float, reap=lambda: None) -> dict:
    """SIGTERM to the group, SIGKILL after ``grace_s`` to what is left
    of it, and back only when no process of it runs (or, a process
    that SIGKILL cannot end, after a second ``grace_s``, named under
    ``left``).  ``reap`` collects the caller's own child, which stays a
    zombie until then.  Returns what had to be killed.  A signal that
    tells the caller to end while this goes on is held until it is
    through, then obeyed: the way out is not itself cut short."""
    fate = {"sigkill": False, "killed": [], "left": []}
    held: list[int] = []
    handlers = {
        signum: signal.signal(signum, lambda n, frame: held.append(n))
        for signum in SIGNALS
    }

    def send(signum) -> None:
        # a group with no member may have given its id to another
        if group_members(pgid):
            try:
                os.killpg(pgid, signum)
            except ProcessLookupError:
                pass

    def gone(limit_s: float) -> bool:
        deadline = time.time() + limit_s
        while True:
            reap()
            if not group_members(pgid):
                return True
            if time.time() > deadline:
                return False
            time.sleep(LOOK_S)

    try:
        send(signal.SIGTERM)
        if not gone(grace_s):
            fate["sigkill"] = True
            fate["killed"] = sorted(group_members(pgid).items())
            send(signal.SIGKILL)
            if not gone(grace_s):
                fate["left"] = sorted(group_members(pgid).items())
    finally:
        for signum, handler in handlers.items():
            signal.signal(signum, handler)
    if held:
        raise SystemExit(128 + held[0])
    return fate


def die_with_parent(grace_s: float = EXIT_GRACE_S) -> None:
    """For a process that must not outlive the one that started it.
    The kernel sends it SIGTERM when that parent dies (Linux's
    ``PR_SET_PDEATHSIG``), and a thread that watches the parent's pid
    sends the same to its whole group, where it leads one, and SIGKILL
    after ``grace_s`` if it is still there to send it."""
    parent = os.getppid()
    try:
        ctypes.CDLL(None, use_errno=True).prctl(
            PR_SET_PDEATHSIG, int(signal.SIGTERM), 0, 0, 0
        )
    except (OSError, AttributeError):
        pass  # not Linux: the thread alone

    def watch() -> None:
        while os.getppid() == parent:
            time.sleep(PARENT_LOOK_S)
        # started by hand it shares its group with its starter's other
        # children, and ends itself alone
        send = os.killpg if os.getpgrp() == os.getpid() else os.kill
        send(os.getpid(), signal.SIGTERM)
        time.sleep(grace_s)
        send(os.getpid(), signal.SIGKILL)

    threading.Thread(target=watch, daemon=True).start()
