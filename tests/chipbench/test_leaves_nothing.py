"""A run leaves nothing behind, however it ends, and a tree says at once
what it cannot run (ISSUE 37): ``run.py`` told to end or killed without
a word, a child that will not go, a committee that commits nothing, a
port that another run's committee holds, a configuration that ``needs``
a part this program lacks.

All on the CPU (``--dry``) on a copy of the benchmark's files that holds
4-node cells, added as ``test_admits_a_cell.py`` adds one.  Every test
has a time limit of its own and ends what it started."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from chipbench import ending, run

from .test_manifest import ROOT, load
from .test_nodedup_cell import checkout_on_ports_of_its_own

#: this file's node ports: the other rehearsals have 21,000 to 23,000
DRY_BASE_PORT = 24_000
TRAFFIC = "low-leaves4"
#: the cells of the copy: ``local4``, and two copies of it that name a
#: part of the program that is there, and one that is not
PLAIN, HAS, LACKS = "leaves4.low", "leaves4.has.low", "leaves4.lacks.low"
NEEDS = {
    PLAIN: None,
    HAS: [
        "hotstuff_tpu.consensus.aggregator:QCMaker",
        "hotstuff_tpu.consensus.aggregator:QCMaker.append",
    ],
    LACKS: ["hotstuff_tpu.consensus.aggregator:NotThereYet"],
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory) -> str:
    root = tmp_path_factory.mktemp("leaves")
    checkout_on_ports_of_its_own(root)
    (root / "benchmark" / "local.py").write_text(
        f"def safe_base_port():\n    return {DRY_BASE_PORT}\n"
    )
    with open(root / "chipbench" / "traffic" / f"{TRAFFIC}.json", "w") as f:
        json.dump({"name": TRAFFIC, "rate_tx_s": 20, "payload_bytes": 512,
                   "ramp_s": 1, "drain_cap_s": 10}, f)  # fmt: skip
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = load("configs", "local4.json")
    for cell, needs in NEEDS.items():
        name = cell.removesuffix(".low")
        config = {**base, "name": name}
        if needs is not None:
            config["needs"] = needs
        with open(root / "chipbench" / "configs" / f"{name}.json", "w") as f:
            json.dump(config, f)
        bench["configs"].append({
            "name": name, "source": base["source"],
            "file": f"chipbench/configs/{name}.json",
            "reduced": base["reduced"], "why": "this file's committee",
        })  # fmt: skip
        bench["workloads"].append({
            "name": cell, "config": name, "traffic": TRAFFIC, "chips": 1,
            "why": "4 nodes, 20 tx/s of 512 B, open loop",
        })  # fmt: skip
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in metric:
                metric["workloads"].append(cell)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


def start_run(checkout: str, cell: str, seconds: int = 60, patch: str = "pass"):
    """``run.py --dry`` on the copy; ``patch`` is Python that sets a
    constant of the module before ``main()``."""
    program = (
        f"import sys; sys.path.insert(0, {checkout!r}); "
        f"from chipbench import run; {patch}; "
        f"sys.argv = ['run.py', '--workload', {cell!r}, '--seed', '7', "
        f"'--seconds', '{seconds}', '--trace', '0', '--dry']; "
        "sys.exit(run.main())"
    )
    return subprocess.Popen(
        [sys.executable, "-c", program], cwd=checkout, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        # the program is not part of the benchmark's copy
        env={**os.environ, "PYTHONPATH": ROOT},
    )  # fmt: skip


def run_dir_of(checkout: str, cell: str) -> str:
    return os.path.join(checkout, "chiprun_out", "chipbench", cell)


def running_with(text: str) -> dict[int, str]:
    """Every live process whose command line holds ``text`` (a zombie
    has none)."""
    found = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            command = ending.command_of(pid)
        except OSError:
            continue
        if text in command:
            found[int(pid)] = command
    return found


def still_running(pids) -> set[int]:
    """Those of ``pids`` that have not ended: a process on its way out
    loses its command line before it closes its sockets, so this reads
    the states of its threads."""
    left = set()
    for pid in pids:
        try:
            if ending.thread_states(pid).strip("ZX"):
                left.add(pid)
        except OSError:
            pass
    return left


def wait_until(condition, limit_s: float, what: str):
    deadline = time.time() + limit_s
    while not condition():
        assert time.time() < deadline, f"after {limit_s} s: {what}"
        time.sleep(0.1)


def listens(port: int) -> bool:
    return run.answering((port,)) is not None


def end_whatever_is_left(process, text: str) -> None:
    if process.poll() is None:
        process.kill()
    process.wait(timeout=30)
    for pid in running_with(text):
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    wait_until(lambda: not running_with(text), 30, "the clean-up's SIGKILL")


@pytest.mark.parametrize("signum", [signal.SIGTERM, signal.SIGKILL])
def test_run_ended_from_outside_leaves_no_committee(checkout, signum):
    """The signal goes to ``run.py``'s pid alone, once the committee
    listens: told to end, ``run.py`` ends the child's group on its way
    out; killed, it is missed by the child.  Either way no process of
    the run is left and the committee's ports are free."""
    run_dir = run_dir_of(checkout, PLAIN)
    process = start_run(checkout, PLAIN)
    try:
        wait_until(
            lambda: os.path.exists(os.path.join(run_dir, "nodes.json"))
            and listens(DRY_BASE_PORT),
            90, "a committee that listens",
        )  # fmt: skip
        started = set(running_with(run_dir))
        assert started, "the child names its run directory"
        os.kill(process.pid, signum)
        stdout, _ = process.communicate(timeout=ending.EXIT_GRACE_S + 15)
        assert process.returncode == (
            -signum if signum == signal.SIGKILL else 128 + signum
        )
        assert '"correct"' not in stdout
        wait_until(
            lambda: not still_running(started) and not running_with(run_dir),
            ending.EXIT_GRACE_S + 5, f"still running: {running_with(run_dir)}",
        )  # fmt: skip
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", DRY_BASE_PORT))
    finally:
        end_whatever_is_left(process, run_dir)


def test_end_child_kills_the_whole_group_and_says_so(monkeypatch):
    """A child in a session of its own that ignores SIGTERM and has
    started a process of its own: after the grace both are SIGKILLed,
    both are gone when ``end_child`` returns, and it names them."""
    monkeypatch.setattr(ending, "EXIT_GRACE_S", 1.0)
    stand_in = (
        "import signal, subprocess, time; "
        "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
        "subprocess.Popen(['sleep', '301']); "
        "print('up', flush=True); time.sleep(302)"
    )
    child = subprocess.Popen(
        [sys.executable, "-c", stand_in], start_new_session=True,
        stdout=subprocess.PIPE, text=True,
    )  # fmt: skip
    try:
        assert child.stdout.readline() == "up\n"
        assert len(ending.group_members(child.pid)) == 2
        began = time.time()
        fate = run.end_child(child, ports=(DRY_BASE_PORT, DRY_BASE_PORT + 3))
        assert time.time() - began < 10
        assert fate["rc_before_signal"] is None
        assert fate["sigkill"] is True and fate["left"] == []
        assert fate["port_left"] is None and fate["ports_free_s"] < 1
        killed = sorted(command for _, command in fate["killed"])
        assert len(killed) == 2 and killed[1] == "sleep 301"
        assert child.poll() == -signal.SIGKILL
        assert ending.group_members(child.pid) == {}
    finally:
        child.stdout.close()
        if ending.group_members(child.pid):
            os.killpg(child.pid, signal.SIGKILL)
        child.wait(timeout=30)


def test_a_process_whose_first_thread_alone_has_ended_still_runs():
    """What a chip's holder looks like for ~6 s after SIGTERM: its first
    thread a zombie, the others still leaving with its ports and the
    chip (``PERF.md`` section 6, PR 37).  The group is not gone until
    they are, and ``end_group`` does not come back before."""
    stand_in = (
        "import ctypes, threading, time; "
        "threading.Thread(target=time.sleep, args=(304,)).start(); "
        "print('up', flush=True); ctypes.CDLL(None).pthread_exit(None)"
    )
    child = subprocess.Popen(
        [sys.executable, "-c", stand_in], start_new_session=True,
        stdout=subprocess.PIPE, text=True,
    )  # fmt: skip
    try:
        assert child.stdout.readline() == "up\n"
        wait_until(
            lambda: ending.thread_states(child.pid).count("Z") == 1,
            10, "a first thread that has ended",
        )  # fmt: skip
        assert list(ending.group_members(child.pid)) == [child.pid]
        fate = ending.end_group(child.pid, 5.0, reap=child.poll)
        assert fate == {"sigkill": False, "killed": [], "left": []}
        assert child.poll() == -signal.SIGTERM
    finally:
        child.stdout.close()
        if child.poll() is None:
            child.kill()
        child.wait(timeout=30)


def test_a_configuration_that_needs_what_is_not_there_is_refused(checkout):
    """Non-zero in seconds, the child's one line on standard error,
    nothing of a device written and no result."""
    run_dir = run_dir_of(checkout, LACKS)
    process = start_run(checkout, LACKS)
    try:
        began = time.time()
        stdout, stderr = process.communicate(timeout=60)
        assert time.time() - began < 10
        assert process.returncode not in (0, None)
        assert (
            "chipbench: configuration leaves4.lacks needs "
            "hotstuff_tpu.consensus.aggregator:NotThereYet, "
            "which this program does not have"
        ) in stderr
        assert "exit 4" in stderr
        assert not os.path.exists(os.path.join(run_dir, "device.json"))
        assert not os.path.exists(os.path.join(run_dir, "nodes.json"))
        assert '"correct"' not in stdout
        assert not running_with(run_dir)
    finally:
        end_whatever_is_left(process, run_dir)


def test_a_configuration_that_needs_what_is_there_runs_as_before(checkout):
    run_dir = run_dir_of(checkout, HAS)
    process = start_run(checkout, HAS, seconds=4)
    try:
        stdout, stderr = process.communicate(timeout=120)
        assert process.returncode == 0, stderr[-2000:]
        result = json.loads(stdout.strip().splitlines()[-1])
        detail = json.loads(stdout.strip().splitlines()[-2])
        assert result["correct"] is True, detail["why_not_correct"]
        assert (result["attempted"], result["failed"]) == (4 * 20, 0)
        # the child's group went on SIGTERM, all of it, before the line
        assert detail["child"].pop("ports_free_s") < 5
        assert detail["child"] == {
            "rc_before_signal": None, "sigkill": False, "killed": [],
            "left": [], "port_left": None,
        }  # fmt: skip
        assert not running_with(run_dir)
    finally:
        end_whatever_is_left(process, run_dir)


def test_a_committee_that_commits_nothing_is_given_up_on(checkout):
    """The boot limit, cut to 2 s, against the set-up of 64 nodes: the
    message, non-zero, no result, nothing left."""
    run_dir = run_dir_of(checkout, "colo64.low")
    process = start_run(checkout, "colo64.low", patch="run.BOOT_LIMIT_S = 2.0")
    try:
        began = time.time()
        stdout, stderr = process.communicate(
            timeout=2 + ending.EXIT_GRACE_S + 30
        )
        assert time.time() - began < 2 + ending.EXIT_GRACE_S
        assert process.returncode not in (0, None)
        assert "chipbench: " in stderr and "(2 s after the start)" in stderr
        assert '"correct"' not in stdout
        assert not running_with(run_dir)
        assert not listens(DRY_BASE_PORT)
    finally:
        end_whatever_is_left(process, run_dir)


def test_a_held_port_is_named_and_nothing_is_started(checkout):
    """A listener on the committee's first port, as a committee left by
    another run would be: non-zero at once, the port and its holder
    named, the holder left alone."""
    run_dir = run_dir_of(checkout, PLAIN)
    with socket.socket() as held:
        held.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        held.bind(("127.0.0.1", DRY_BASE_PORT))
        held.listen()
        process = start_run(checkout, PLAIN)
        try:
            began = time.time()
            stdout, stderr = process.communicate(timeout=60)
            assert time.time() - began < 10
            assert process.returncode not in (0, None)
            assert f"port {DRY_BASE_PORT} of the committee's" in stderr
            assert f"pid {os.getpid()}:" in stderr
            assert stdout == ""
            assert not os.path.exists(os.path.join(run_dir, "node.log"))
            assert held.fileno() >= 0 and listens(DRY_BASE_PORT)
        finally:
            end_whatever_is_left(process, run_dir)


def test_a_listener_that_no_process_holds_is_given_the_grace(monkeypatch):
    """What a chip's holder leaves for some seconds after it has gone,
    and a run killed from outside cannot wait for: the next run waits
    for the kernel to close it, and refuses one that stays."""
    port = DRY_BASE_PORT + 100
    monkeypatch.setattr(run, "holder_of", lambda port: None)
    monkeypatch.setattr(run, "STARTED", time.time())
    with socket.socket() as held:
        held.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        held.bind(("127.0.0.1", port))
        held.listen()
        closing = threading.Timer(0.5, held.close)
        closing.start()
        try:
            began = time.time()
            run.refuse_held_ports((port, port))
            assert 0.4 < time.time() - began < 5
        finally:
            closing.join(timeout=10)
    with socket.socket() as held:
        held.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        held.bind(("127.0.0.1", port))
        held.listen()
        monkeypatch.setattr(ending, "EXIT_GRACE_S", 0.5)
        monkeypatch.setattr(run, "STARTED", time.time())
        with pytest.raises(SystemExit, match=f"port {port} .*by no process"):
            run.refuse_held_ports((port, port))


def test_a_signal_during_the_ending_waits_for_it():
    """``end_group`` holds a second SIGTERM until the group is gone,
    then obeys it: the way out is not itself cut short."""
    child = subprocess.Popen(["sleep", "303"], start_new_session=True)
    sent = []

    def reap():
        if not sent:
            sent.append(os.kill(os.getpid(), signal.SIGTERM))
        return child.poll()

    before = signal.getsignal(signal.SIGTERM)
    try:
        with pytest.raises(SystemExit) as leaving:
            ending.end_group(child.pid, 5.0, reap=reap)
        assert leaving.value.code == 128 + signal.SIGTERM
        assert child.poll() == -signal.SIGTERM
        assert ending.group_members(child.pid) == {}
        assert signal.getsignal(signal.SIGTERM) is before
    finally:
        if child.poll() is None:
            child.kill()
        child.wait(timeout=30)
