"""The benchmark's command: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It reads the cell from ``BENCHMARK.json``, its configuration, traffic
and metrics by name from the files under ``chipbench/``, starts the
committee in a child that holds the chip (``child.py``), offers the load
from this process (``gen.py``), reduces the committee's log with the
metrics' readers, checks the guarantees (``check.py``) and prints the
result as the last line.  It never imports jax.  ``--dry`` is the CPU
rehearsal the tests make; it is not a cell and says ``platform: cpu``.
However it ends, nothing it started is left (``ending.py``).
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import shutil
import socket
import subprocess
import sys
import time

STARTED = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import check, ending, trace  # noqa: E402
from chipbench.gen import Generator, Plan  # noqa: E402
from chipbench.logs import CommitteeLog  # noqa: E402
from chipbench.reduce import Run  # noqa: E402

#: how often the committee's log is read while the run goes on
POLL_S = 0.25
#: seconds of the window that a traced run hands to the profiler
TRACE_S = 7.0
#: from the start of ``run.py`` to the first commit, or the run is given
#: up.  The committee binds its ports only after a warm-up that compiles
#: on a checkout's first run: the slowest first set-up on record is
#: 105.8 s (ledger, PR 35, ``colo64.low``) and a checkout's first run
#: with ``native/`` to build read 75.9 s and 94.8 s (my chip runs, PRs 35
#: and 37), so this is about three times the slowest seen; it was 1,000 s
#: until PR 37
BOOT_LIMIT_S = 300.0


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def find_cell(name: str) -> tuple[dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == name]
    if not cells:
        raise SystemExit(f"chipbench: no cell '{name}' in BENCHMARK.json")
    return bench, cells[0]


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    return [
        m
        for m in bench[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]


def read_metric(name: str, run: Run) -> float | None:
    """The metric's reader, found by the name in its own file."""
    module, function = load("layers", name)["reader"].split(":")
    reader = getattr(
        importlib.import_module(f"chipbench.readers.{module}"), function
    )
    return reader(run)


def read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


async def wait_for(path: str, deadline: float, alive) -> bool:
    while not os.path.exists(path):
        if time.time() > deadline or not alive():
            return False
        await asyncio.sleep(0.05)
    return True


def given_up(what: str, child, log_path: str) -> SystemExit:
    """How a run that gets no committee leaves: what did not come,
    whether the child had ended or the boot limit had passed, and the
    end of the child's log (its last words, if it refused to start)."""
    rc = child.poll()
    why = (
        f"{BOOT_LIMIT_S:.0f} s after the start" if rc is None
        else f"the child had ended, exit {rc}"
    )
    try:
        with open(log_path, errors="replace") as f:
            f.seek(max(0, os.path.getsize(log_path) - 1500))
            tail = f.read()
    except OSError:
        tail = ""
    return SystemExit(f"chipbench: {what} ({why})\n{tail}".rstrip())


def holder_of(port: int) -> str | None:
    """The process that listens on a port, from ``/proc/net/tcp`` and
    ``/proc/*/fd`` as far as this user may read them; None where no
    process holds the listener."""
    inodes = set()
    for table in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            with open(table) as f:
                rows = [line.split() for line in f.read().splitlines()[1:]]
        except OSError:
            continue
        inodes |= {
            row[9] for row in rows
            if row[3] == "0A" and int(row[1].rsplit(":", 1)[1], 16) == port
        }
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                link = os.readlink(f"/proc/{pid}/fd/{fd}")
                if link.startswith("socket:[") and link[8:-1] in inodes:
                    return f"pid {pid}: {ending.command_of(pid)}"
        except OSError:
            continue
    return None


def committee_ports(nodes: int) -> tuple[int, int]:
    """The first and the last port the committee binds: every run's
    are the same (``safe_base_port()`` is one fixed base a host)."""
    try:
        from benchmark.local import safe_base_port
    except ImportError as e:
        raise SystemExit(f"chipbench: no program to import here: {e}")
    base = safe_base_port()
    return base, base + nodes - 1


def answering(ports) -> int | None:
    """The first of ``ports`` on which a listener answers a connect (a
    socket in TIME_WAIT does not)."""
    for port in ports:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1.0).close()
        except OSError:
            continue
        return port
    return None


def refuse_held_ports(ports: tuple[int, int]) -> None:
    """A committee left by another run would take this run's load and
    answer for it, so a port that a process holds ends the run at once:
    the holder is named, not killed, since this run did not start it.
    A listener that no process holds is one the kernel is still
    closing, for seconds after a chip's holder has gone (my chip run,
    PR 37): a run killed from outside leaves such, and they are given
    the grace to go."""
    port = answering(ports)
    while port is not None:
        holder = holder_of(port)
        if holder or time.time() - STARTED > ending.EXIT_GRACE_S:
            raise SystemExit(
                f"chipbench: port {port} of the committee's {ports[0]}-"
                f"{ports[1]} is held ({holder or 'by no process in /proc'})"
            )
        time.sleep(ending.LOOK_S)
        port = answering(ports)


async def drive(args, config, traffic, run_dir, child, log_path) -> Run:
    """Set-up, ramp, window and drain; returns the finished run."""
    alive = lambda: child.poll() is None  # noqa: E731
    nodes = config["nodes"]
    plan = Plan(traffic, nodes, args.seed, args.seconds)
    log = CommitteeLog()
    setup = {"child_started_s": child.started - STARTED}
    deadline = STARTED + BOOT_LIMIT_S
    if not await wait_for(
        os.path.join(run_dir, "nodes.json"), deadline, alive
    ):
        raise given_up(
            "the child never wrote its committee", child, log_path
        )
    addresses = [tuple(n["address"]) for n in read_json(
        os.path.join(run_dir, "nodes.json")
    )]
    gen = Generator(addresses, plan)
    run = Run(config, traffic, plan, log, gen.sent_at, gen.refused, 0.0)
    run.setup = setup
    try:
        try:
            await gen.connect(deadline, alive)
        except OSError as e:
            raise given_up(f"a node never listened: {e}", child, log_path)
        setup["connected_s"] = time.time() - STARTED
        gen.prime()
        while log.first_commit is None:
            if not alive() or time.time() > deadline:
                raise given_up(
                    "the committee committed nothing", child, log_path
                )
            await asyncio.sleep(0.02)
            log.poll(log_path)
        setup["first_commit_s"] = log.first_commit - STARTED
        # set-up ends here: every connection is up, a block is committed
        setup["setup_s"] = time.time() - STARTED
        run.t_ramp = time.time() + 0.01
        stop = asyncio.Event()
        sending = asyncio.ensure_future(gen.run(run.t_ramp, stop))
        trace_at = run.t0 + (plan.seconds - TRACE_S) / 2
        pending = None
        while alive():
            await asyncio.sleep(POLL_S)
            log.poll(log_path)
            now = time.time()
            if args.trace and now >= trace_at:
                with open(os.path.join(run_dir, "trace.request"), "w") as f:
                    f.write(str(min(TRACE_S, plan.seconds)))
                trace_at = float("inf")
            if now < run.t1:
                continue
            if now >= run.t1 + plan.after_s:
                break
            # the drain: the schedule goes on until every payload that
            # was due in the window is committed on a quorum
            if pending is None:
                pending = set(plan.window()) - gen.refused
            pending = {
                k
                for k in pending
                if log.payload_commit(plan.ids[k], run.quorum) is None
            }
            if not pending:
                break
        stop.set()
        await sending
        log.poll(log_path)
        run.t_end = time.time()
    finally:
        gen.close()
    return run


def read_memory(child, run_dir: str) -> None:
    """Have the child write ``memory.json`` while it still holds the
    chip."""
    if child.poll() is not None:
        return
    open(os.path.join(run_dir, "memory.request"), "w").close()
    deadline = time.time() + 5.0
    while (
        not os.path.exists(os.path.join(run_dir, "memory.json"))
        and time.time() < deadline
    ):
        time.sleep(0.05)


def end_child(child, ports: tuple[int, int]) -> dict:
    """End the child and whatever it started, its whole session, and
    wait until no process of it runs and no port of the committee
    answers: the kernel goes on closing a chip holder's sockets after
    its last thread has gone, and the next run would meet them."""
    rc = child.poll()
    ended = ending.end_group(child.pid, ending.EXIT_GRACE_S, reap=child.poll)
    began = time.time()
    left = answering(ports)
    while left is not None and time.time() - began < ending.EXIT_GRACE_S:
        time.sleep(ending.LOOK_S)
        left = answering(ports)
    return {
        "rc_before_signal": rc, **ended,
        "ports_free_s": time.time() - began, "port_left": left,
    }


def read_trace(run_dir: str) -> dict | None:
    """The device's events, read by a process of its own once the chip
    is free (reading a trace imports jax), then summarised here."""
    done = read_json(os.path.join(run_dir, "trace.done"))
    if done is None:
        return None
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace.py"),
         os.path.join(run_dir, "trace")],
        capture_output=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=120,
    )
    if out.returncode != 0:
        sys.stderr.write(out.stderr.decode("utf-8", "replace")[-2000:])
        return None
    devices = json.loads(out.stdout)
    with open(os.path.join(run_dir, "trace_events.json"), "w") as f:
        json.dump(devices, f)
    return trace.summarise_devices(devices, done["stopped"] - done["started"])


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dry", action="store_true")
    args = parser.parse_args()

    bench, cell = find_cell(args.workload)
    config = load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    run_dir = os.path.join(ROOT, "chiprun_out", "chipbench", cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    log_path = os.path.join(run_dir, "node.log")

    env = {**os.environ, **config["env"], "TZ": "UTC"}
    if args.dry:
        env["JAX_PLATFORMS"] = "cpu"
    command = [
        sys.executable, os.path.join(HERE, "child.py"),
        "--run-dir", run_dir,
        "--config", os.path.join(HERE, "configs", cell["config"] + ".json"),
        "--seed", str(args.seed),
        "--chips", str(cell["chips"]),
    ] + (["--dry"] if args.dry else [])
    ending.exit_on_signals()
    ports = committee_ports(config["nodes"])
    refuse_held_ports(ports)
    # a session of its own: the child and what it starts are one group
    with open(log_path, "wb") as log_file:
        child = subprocess.Popen(
            command, stdout=log_file, stderr=subprocess.STDOUT,
            env=env, cwd=ROOT, start_new_session=True,
        )
    try:
        child.started = time.time()
        run = asyncio.run(
            drive(args, config, traffic, run_dir, child, log_path)
        )
        read_memory(child, run_dir)
    finally:
        fate = end_child(child, ports)
    run.log.poll(log_path)
    device = read_json(os.path.join(run_dir, "device.json"))
    if device is None:
        raise SystemExit("chipbench: the child named no device")
    device.update(
        read_json(os.path.join(run_dir, "memory.json"))
        or {"memory_peak_bytes": 0}
    )
    if args.trace:
        run.trace = read_trace(run_dir)
        if run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]

    why_not = check.violations(run.log, config["nodes"])
    if fate["rc_before_signal"] is not None:
        why_not.append(f"the child died: exit {fate['rc_before_signal']}")
    if fate["sigkill"]:
        why_not.append(
            f"the child's group had to be SIGKILLed: {fate['killed']}"
        )
    if fate["port_left"] is not None:
        why_not.append(f"port {fate['port_left']} still answers")
    if run.log.tracebacks:
        why_not.append(f"{run.log.tracebacks} traceback(s) in the log")

    # every metric of the cell goes into the detail; the line carries the
    # end-to-end ones, or in a traced run the per-layer ones
    every = {
        kind: {
            m["name"]: {"value": read_metric(m["name"], run), "unit": m["unit"]}
            for m in metrics_of(bench, cell["name"], kind)
        }
        for kind in ("end_to_end", "per_layer")
    }
    metrics = {
        name: metric
        for name, metric in every[
            "per_layer" if args.trace else "end_to_end"
        ].items()
        if metric["value"] is not None
    }
    # attempted: every payload due in the window; failed: those refused,
    # or not committed on a quorum when the drain ended
    window = run.plan.window()
    failed = sum(run.commit_at(k) is None for k in window)
    result = {
        "correct": not why_not,
        "attempted": len(window),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
    lat = run.window_latencies_ms()
    third = max(1, len(lat) // 3)
    detail = {
        "workload": cell["name"], "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace, "dry": args.dry,
        "why_not_correct": why_not, "child": fate, "setup": run.setup,
        "phases": {"ramp": run.t_ramp, "window": run.t0,
                   "window_end": run.t1, "end": run.t_end},
        "offset": run.plan.offset,
        "latency_p50_ms_by_third": [
            sorted(part)[len(part) // 2]
            for part in (lat[:third], lat[third:2 * third], lat[2 * third:])
            if part
        ],
        "every_metric": {
            name: metric["value"]
            for kind in every.values()
            for name, metric in kind.items()
        },
    }
    with open(os.path.join(run_dir, "detail.json"), "w") as f:
        json.dump({"result": result, "detail": detail}, f, indent=1)
    # the nodes' stores and the raw trace are large and read by nothing now
    for name in os.listdir(run_dir):
        if name.startswith(".db_") or name == "trace":
            shutil.rmtree(os.path.join(run_dir, name), ignore_errors=True)
    print(json.dumps(detail))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
