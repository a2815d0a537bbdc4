"""Local benchmark: a committee of node subprocesses + a client.

Parity target: reference ``LocalBench`` (benchmark/benchmark/local.py:
12-121): kill leftovers -> keygen per node -> write committee/parameters
JSON -> launch clients and nodes detached with stderr to log files ->
sleep for the duration -> kill -> parse logs. tmux is replaced by plain
``subprocess.Popen`` (same detached-process semantics, no extra
dependency); cargo build is replaced by nothing (Python needs no build
step — the C++ store engine, when built, is picked up automatically).

One process per chip: a device verifier is only accepted with
``in_process=True`` — one ``node run-many`` process owns the chip; the
harness parent and the client never import jax.
"""

from __future__ import annotations

import os
import shutil
import signal
import subprocess
import sys
import time

from hotstuff_tpu.consensus import Committee, Parameters
from hotstuff_tpu.node.config import Secret, write_committee, write_parameters

from .logs import LogParser
from .utils import METRICS_PORT_OFFSET, BenchError, PathMaker, Print

BASE_PORT = 26_500

DEVICE_VERIFIERS = ("tpu", "tpu-sharded", "mesh")


def safe_base_port(
    range_file: str = "/proc/sys/net/ipv4/ip_local_port_range",
) -> int:
    """``BASE_PORT``, moved below the host's ephemeral port range when it
    lies inside it.  A listener cannot bind a port on which a client's
    ephemeral socket sits, TIME_WAIT included, and a committee and its
    client make thousands of those: where the range covers the node
    ports (the v5e host's is 16000-65535) a node's bind fails now and
    then with EADDRINUSE and takes the run with it."""
    try:
        with open(range_file) as f:
            lo, hi = (int(x) for x in f.read().split())
    except (OSError, ValueError):
        return BASE_PORT
    if not lo <= BASE_PORT <= hi:
        return BASE_PORT
    # room for 1,000 node ports and as many metrics ports above them
    base = lo - METRICS_PORT_OFFSET - 1_000
    if base < 1_024:
        raise BenchError(
            f"no room for the committee's ports below the host's "
            f"ephemeral range {lo}-{hi}"
        )
    return base


class LocalBench:
    def __init__(
        self,
        nodes: int = 4,
        rate: int = 1_000,
        duration: float = 20.0,
        faults: int = 0,
        timeout_delay: int = 5_000,
        sync_retry_delay: int = 10_000,
        verifier: str = "cpu",
        transport: str = "asyncio",
        base_port: int | None = None,
        scheme: str = "ed25519",
        in_process: bool = False,
        tx_size: int = 512,
        wan: bool = False,
        payload_homes: int = 1,
        no_claim_dedup: bool = False,
        journal: bool = False,
        profile: bool = False,
        health: bool = False,
    ):
        self.nodes = nodes
        self.rate = rate
        self.tx_size = tx_size
        self.payload_homes = payload_homes
        # HOTSTUFF_NO_CLAIM_DEDUP=1 for every node process: a process's
        # verify service keeps one lane for every submitted claim.  With
        # one node a process nothing is shared, so nothing changes; an
        # --in-process committee keeps its one dispatch stream and has
        # every node's own copy of every certificate verified
        self.no_claim_dedup = no_claim_dedup
        # WAN emulation: write a 5-region link-delay spec and point the
        # committee at it (hotstuff_tpu/network/wan.py)
        self.wan = wan
        if wan and transport == "native":
            # the native reactor does its own I/O and applies no link
            # delays — a '-wan'-labeled result from it would feed
            # undelayed localhost numbers into the WAN comparison plot
            raise BenchError(
                "--wan requires the asyncio transport (the native "
                "reactor applies no link delays)"
            )
        if verifier in DEVICE_VERIFIERS and not in_process:
            raise BenchError(
                f"--verifier {verifier} needs --in-process: a chip belongs "
                "to one process, and without it every committee member "
                "is a process of its own"
            )
        self.duration = duration
        self.faults = faults
        self.timeout_delay = timeout_delay
        self.sync_retry_delay = sync_retry_delay
        self.verifier = verifier
        self.transport = transport
        self.base_port = (
            base_port if base_port is not None else safe_base_port()
        )
        self.scheme = scheme
        # journal=True: flight recorder on in every node (JSONL ring
        # segments under logs/journals/, merged by benchmark/traces.py)
        self.journal = journal
        # profile=True: verify-pipeline span profiler on in every node;
        # with journal also on, the spans land in the journals and the
        # merged trace grows a "verify pipeline" track per node process
        self.profile = profile
        # health=True: live health plane on in every node — online
        # anomaly detectors + campaign recorder, and a /metrics+/delta
        # endpoint per node at consensus port + METRICS_PORT_OFFSET so
        # `python -m benchmark watch` can attach to the running fleet
        self.health = health
        # in_process=True: the whole committee co-locates in ONE node
        # process (`run-many`, the reference's in-process testbed shape,
        # main.rs:102-148).  On a host with fewer cores than nodes the
        # per-process harness measures the OS scheduler, not the
        # protocol; this mode shares one asyncio loop instead.
        self.in_process = in_process
        self._procs: list[subprocess.Popen] = []
        # node index -> its (latest) subprocess — lets subclasses target
        # individual nodes (ChaosBench crash/restart schedules)
        self._node_procs: dict[int, subprocess.Popen] = {}
        self._client_proc: subprocess.Popen | None = None
        # (command, exit status) of every process found dead when the
        # window closed, a client that finished cleanly excepted
        self.died: list[tuple[str, int]] = []
        # extra environment for every spawned process — subclass hook
        # (ChaosBench injects HOTSTUFF_FAULTS here)
        self.extra_env: dict[str, str] = {}

    # ---- setup/teardown ----------------------------------------------------

    def _cleanup_files(self) -> None:
        for i in range(self.nodes):
            shutil.rmtree(PathMaker.db_path(i), ignore_errors=True)
        shutil.rmtree(PathMaker.logs_path(), ignore_errors=True)
        os.makedirs(PathMaker.logs_path(), exist_ok=True)

    def _kill_processes(self) -> None:
        for proc in self._procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        # a process that holds a chip takes seconds to leave, whichever
        # signal ends it (CHANGES.md, ISSUE 22); the harness returns only
        # once every process is gone, so the chip is free for the next
        grace = 30.0 if self.verifier in DEVICE_VERIFIERS else 5.0
        deadline = time.time() + grace
        for proc in self._procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                # NOTE: chip_smoke.py fails a run on this line
                Print.warn(
                    f"pid {proc.pid} did not exit on SIGTERM within "
                    f"{grace:.0f} s: killed"
                )
                proc.kill()
                proc.wait()
        self._procs.clear()
        self._node_procs.clear()

    def _dead_processes(self) -> list[tuple[str, int]]:
        """(command, exit status) of every process that is not running
        any more.  A node must still be up; the client may have
        finished, but not failed."""
        return [
            (" ".join(proc.args[1:5]), proc.returncode)
            for proc in self._procs
            if proc.poll() is not None
            and not (proc is self._client_proc and proc.returncode == 0)
        ]

    def _config(self) -> None:
        keys = [Secret.new(self.scheme) for _ in range(self.nodes)]
        committee = Committee.new(
            [
                (secret.name, 1, ("127.0.0.1", self.base_port + i))
                for i, secret in enumerate(keys)
            ],
            scheme=self.scheme,
            pops={s.name: s.pop for s in keys if s.pop is not None},
        )
        write_committee(committee, PathMaker.committee_file())
        if self.wan:
            import json

            from hotstuff_tpu.network.wan import build_spec

            spec = build_spec(
                [("127.0.0.1", self.base_port + i) for i in range(self.nodes)]
            )
            with open(self._wan_spec_path(), "w") as f:
                json.dump(spec, f)
        write_parameters(
            Parameters(
                timeout_delay=self.timeout_delay,
                sync_retry_delay=self.sync_retry_delay,
            ),
            PathMaker.parameters_file(),
        )
        for i, secret in enumerate(keys):
            secret.write(PathMaker.key_file(i))

    @staticmethod
    def _wan_spec_path() -> str:
        return os.path.join(PathMaker.base_path(), ".wan.json")

    def _spawn(
        self, cmd: list[str], log_file: str, append: bool = False
    ) -> subprocess.Popen:
        # append=True: a node restarted mid-run (chaos crash/restart)
        # keeps its pre-crash log — both lifetimes feed the log parser
        # and the invariant checker
        f = open(log_file, "a" if append else "w")
        # repo root (the directory holding hotstuff_tpu/), NOT cwd — the
        # harness must work from any working directory
        import hotstuff_tpu

        root = os.path.dirname(os.path.dirname(os.path.abspath(hotstuff_tpu.__file__)))
        wan_env = (
            {"HOTSTUFF_WAN_SPEC": self._wan_spec_path()} if self.wan else {}
        )
        if self.no_claim_dedup:
            wan_env["HOTSTUFF_NO_CLAIM_DEDUP"] = "1"
        if self.journal:
            wan_env["HOTSTUFF_JOURNAL"] = "1"
            wan_env["HOTSTUFF_JOURNAL_DIR"] = os.path.abspath(
                PathMaker.journals_path()
            )
        if self.profile:
            wan_env["HOTSTUFF_PROFILE"] = "1"
        if self.health:
            wan_env["HOTSTUFF_HEALTH"] = "1"
        proc = subprocess.Popen(
            cmd,
            stdout=f,
            stderr=subprocess.STDOUT,
            env={
                **os.environ,
                **wan_env,
                **self.extra_env,
                # PREPEND the repo root, keeping the caller's entries
                "PYTHONPATH": os.pathsep.join(
                    p
                    for p in (root, os.environ.get("PYTHONPATH", ""))
                    if p
                ),
            },
        )
        self._procs.append(proc)
        return proc

    def _node_cmd(self, i: int) -> list[str]:
        cmd = [
            sys.executable,
            "-m",
            "hotstuff_tpu.node",
            "-vv",
            "run",
            "--keys",
            PathMaker.key_file(i),
            "--committee",
            PathMaker.committee_file(),
            "--store",
            PathMaker.db_path(i),
            "--parameters",
            PathMaker.parameters_file(),
            "--verifier",
            self.verifier,
            "--transport",
            self.transport,
        ]
        if self.health:
            # deterministic scrape address: consensus port + fixed
            # offset, the same derivation `benchmark watch` applies to
            # the committee file
            cmd += [
                "--metrics-port",
                str(self.base_port + METRICS_PORT_OFFSET + i),
            ]
        return cmd

    def _client_cmd(self, py: str) -> list[str]:
        """The client process command line — subclass hook (LoadBench
        replaces the fixed-burst client with the Poisson fleet)."""
        return [
            py,
            "-m",
            "hotstuff_tpu.node.client",
            "--committee",
            PathMaker.committee_file(),
            "--rate",
            str(self.rate),
            "--size",
            str(self.tx_size),
            "--homes",
            str(self.payload_homes),
            "--duration",
            str(self.duration),
            "--warmup",
            "2",
            "--faults",
            str(self.faults),
        ]

    def _spawn_node(self, i: int, append: bool = False) -> subprocess.Popen:
        """Boot (or, with ``append=True``, re-boot) node ``i`` as its
        own process.  The store persists across restarts, so a respawned
        node rejoins from its pre-crash chain state."""
        proc = self._spawn(
            self._node_cmd(i), PathMaker.node_log_file(i), append=append
        )
        self._node_procs[i] = proc
        return proc

    # ---- the run -----------------------------------------------------------

    def run(self) -> LogParser:
        Print.heading(
            f"Local bench: {self.nodes} nodes ({self.faults} faults), "
            f"{self.rate} tx/s, {self.duration:.0f}s, verifier={self.verifier}"
        )
        self._cleanup_files()
        self._config()

        py = sys.executable
        try:
            # Boot the committee (skip `faults` nodes — crash-fault
            # injection, reference local.py:75-76).
            if self.in_process:
                run_many_cmd = [
                    py,
                    "-m",
                    "hotstuff_tpu.node",
                    "-vv",
                    "run-many",
                    "--keys",
                    ",".join(
                        PathMaker.key_file(i)
                        for i in range(self.nodes - self.faults)
                    ),
                    "--committee",
                    PathMaker.committee_file(),
                    "--store-prefix",
                    os.path.join(PathMaker.base_path(), ".db_"),
                    "--parameters",
                    PathMaker.parameters_file(),
                    "--verifier",
                    self.verifier,
                    "--transport",
                    self.transport,
                ]
                if self.health:
                    # one co-located process: node 0's derived port
                    # serves the whole committee's /delta
                    run_many_cmd += [
                        "--metrics-port",
                        str(self.base_port + METRICS_PORT_OFFSET),
                    ]
                self._spawn(run_many_cmd, PathMaker.node_log_file(0))
            else:
                for i in range(self.nodes - self.faults):
                    self._spawn_node(i)

            # Launch the producer-path client (subclass hook: LoadBench
            # swaps in the credit-aware open-loop fleet, loadgen.py).
            self._client_proc = self._spawn(
                self._client_cmd(py), PathMaker.client_log_file()
            )

            # Wait for the client to actually START sending before timing
            # the measurement window: boot cost varies hugely (CPU runs
            # boot in ~a second; a device verifier pays a kernel warmup
            # of tens of seconds, more on a cold compilation cache), and
            # a fixed sleep would kill a device committee mid-warmup.
            boot_deadline = time.time() + max(60.0, 4.0 * self.nodes) + (
                300.0 if self.verifier in DEVICE_VERIFIERS else 0.0
            )
            started = False
            while time.time() < boot_deadline:
                try:
                    with open(PathMaker.client_log_file()) as f:
                        if "Start sending transactions" in f.read():
                            started = True
                            break
                except OSError:
                    pass
                if any(p.poll() is not None for p in self._procs):
                    break  # something died — parse what we have
                time.sleep(0.5)
            if not started:
                Print.warn("client never started sending (boot timeout)")
            if started or not self._dead_processes():
                self._measurement_window(started)
            # else: a process died during boot — there is no window
            self.died = self._dead_processes()
        except (OSError, subprocess.SubprocessError) as e:
            raise BenchError(f"Failed to run benchmark: {e}") from e
        finally:
            self._kill_processes()

        return LogParser.process(PathMaker.logs_path())

    def _measurement_window(self, started: bool) -> None:
        """Wait out the measurement window.  Subclass hook: ChaosBench
        overrides this to drive the crash/restart schedule while the
        committee runs."""
        time.sleep(self.duration + 4)  # the window + drain margin
