"""What a co-located committee's process says about its boot and its
connections: the ``Boot stats:`` line ``run-many`` prints once, the
``fds=`` and ``conn_opens=`` counters of the ``Host stats:`` line, and
connections that open lazily, on a peer's first message (the pools stay
unbounded at 4 nodes, so each sender reaches each peer once)."""

import inspect
import os
import re
import signal
import subprocess
import sys
import time
from types import SimpleNamespace

from chipbench.readers import boot, connstats
from chipbench.readers import hoststats as host_lines
from hotstuff_tpu.consensus import Committee, Parameters
from hotstuff_tpu.crypto.scheme import keygen_deterministic
from hotstuff_tpu.network.pool import CONN_COUNTS
from hotstuff_tpu.node import main as node_main
from hotstuff_tpu.node.config import Secret, write_committee, write_parameters
from hotstuff_tpu.telemetry import hoststats

from .common import fresh_base_port

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RE_HOST = re.compile(r"Host stats: (.*)")
RE_BOOT = re.compile(r"Boot stats: (.*)")


def test_the_fd_probe_is_gone_and_the_count_is_off_the_loop():
    """The 5 s listing of ``/proc/self/fd`` on the event loop is gone;
    the count is the ``Host stats:`` line's, made on a worker thread."""
    source = inspect.getsource(node_main)
    assert "_fd_probe" not in source and "fd-probe" not in source
    assert "run_in_executor(None, count_fds)" in inspect.getsource(
        hoststats.HostStats.run
    )
    assert abs(hoststats.count_fds() - len(os.listdir("/proc/self/fd"))) <= 2


def test_fds_and_conn_opens_parse_on_the_host_stats_line():
    before = CONN_COUNTS.opens
    CONN_COUNTS.opens += 3
    try:
        line = hoststats.HostStats().line(fds=1234)
    finally:
        CONN_COUNTS.opens = before
    counters = dict(item.split("=") for item in line.split())
    assert counters["fds"] == "1234"
    assert int(counters["conn_opens"]) == before + 3


class FakeRun:
    """What the two readers take of a ``Run``: the log's lines as they
    keep them, the blocks made and the window's ends."""

    def __init__(self, text: str, made: dict):
        self._boot_stats = boot.stats_of(text)
        self._host_stats = host_lines.lines_of(text)
        self.log = SimpleNamespace(created=made)
        self.t0, self.t1 = T0 + 10, T0 + 55


T0 = 1_767_225_600.0  # 2026-01-01T00:00:00Z


def test_the_readers_of_the_boot_and_host_stats_lines():
    stamp = "2026-01-01T00:00:{:06.3f}Z [INFO] {} {}"
    lines = [
        stamp.format(1.0, "hotstuff_tpu.node.main",
                     "Boot stats: nodes=4 keys_s=0.012 nodes_s=0.250 warm_s=0.000"),
        stamp.format(10.0, "hotstuff_tpu.telemetry.hoststats",
                     "Host stats: elapsed_s=5.0 conn_opens=24 fds=120"),
        stamp.format(50.0, "hotstuff_tpu.telemetry.hoststats",
                     "Host stats: elapsed_s=45.0 conn_opens=824 fds=130"),
    ]  # fmt: skip
    made = {f"b{i}": (T0 + 11 + i, "n", i, []) for i in range(40)}
    run = FakeRun("\n".join(lines), made)
    assert boot.keys_s(run) == 0.012 and boot.nodes_s(run) == 0.25
    assert connstats.conn_opens_per_round(run) == 800 / 40
    # a parent's lines: no Boot stats line, no counter
    bare = FakeRun(
        "\n".join(line.split(" conn_opens")[0] for line in lines[1:]), made
    )
    assert boot.keys_s(bare) is None
    assert connstats.conn_opens_per_round(bare) is None


def committee_files(tmp_path, n: int) -> list[str]:
    base = fresh_base_port()
    secrets = [
        Secret(*keygen_deterministic("ed25519", b"c" * 32, i), "ed25519")
        for i in range(n)
    ]
    write_committee(
        Committee.new(
            [(s.name, 1, ("127.0.0.1", base + i)) for i, s in enumerate(secrets)]
        ),
        str(tmp_path / "committee.json"),
    )
    write_parameters(Parameters(), str(tmp_path / "parameters.json"))
    key_files = []
    for i, secret in enumerate(secrets):
        key_files.append(str(tmp_path / f"node_{i}.json"))
        secret.write(key_files[-1])
    return key_files


def test_run_many_boots_once_and_opens_each_connection_once(tmp_path):
    """4 nodes under load for a few rotations: one ``Boot stats:`` line,
    and connections that open on a peer's first message, each sender to
    each peer once (the core's for votes and timeouts, the proposer's
    for blocks: 2 n (n - 1) in all), and then stay up: ``conn_opens``
    no longer moves while the committee keeps committing."""
    n = 4
    key_files = committee_files(tmp_path, n)
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    log_path = tmp_path / "node.log"
    with open(log_path, "wb") as log_file:
        committee = subprocess.Popen(
            [sys.executable, "-m", "hotstuff_tpu.node", "-vv", "run-many",
             "--keys", ",".join(key_files),
             "--committee", str(tmp_path / "committee.json"),
             "--store-prefix", str(tmp_path / ".db_"),
             "--parameters", str(tmp_path / "parameters.json"),
             "--verifier", "cpu"],
            stdout=log_file, stderr=subprocess.STDOUT, env=env, cwd=tmp_path,
        )  # fmt: skip
    client = subprocess.Popen(
        [sys.executable, "-m", "hotstuff_tpu.node.client",
         "--committee", str(tmp_path / "committee.json"),
         "--rate", "50", "--size", "512", "--duration", "30", "--warmup", "1"],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, env=env,
        cwd=tmp_path,
    )  # fmt: skip
    try:
        deadline = time.time() + 90
        lines: list[dict] = []
        while time.time() < deadline and committee.poll() is None:
            time.sleep(0.5)
            lines = [
                dict(item.split("=") for item in m.group(1).split())
                for m in RE_HOST.finditer(log_path.read_text())
            ]
            if len(lines) >= 4:
                break
        assert committee.poll() is None, log_path.read_text()[-2000:]
    finally:
        for proc in (client, committee):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in (client, committee):
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    text = log_path.read_text()
    assert "Traceback" not in text and "bounding per-sender" not in text
    (boot_line,) = RE_BOOT.findall(text)
    stats = dict(item.split("=") for item in boot_line.split())
    assert set(stats) == {"nodes", "keys_s", "nodes_s", "warm_s"}
    assert stats["nodes"] == "4" and float(stats["warm_s"]) == 0.0
    assert len(lines) >= 4, text[-2000:]
    assert all(int(line["fds"]) > 2 * 2 * n * (n - 1) for line in lines)
    opens = [int(line["conn_opens"]) for line in lines]
    assert opens[-1] == opens[-2] == opens[-3] == 2 * n * (n - 1), opens
    assert text.count("Committed ") > 3 * n
