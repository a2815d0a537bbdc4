"""What the host was doing, from the program's own spans in the
profiler's trace (``chipbench/hostspans.py`` has the arithmetic and
says what each span is).

``Run`` holds no run directory, so this finds
``chiprun_out/chipbench/<cell>`` from the configuration's and the
traffic file's names through ``BENCHMARK.json``, and reads the trace
there before ``run.py`` deletes it: once a run, in a process of its own
(reading a trace imports jax), as ``run.py`` reads the device's events.
It writes ``host_breakdown.json`` beside ``detail.json``.  An untraced
run, or a program without spans (a parent commit), gives None for every
metric here, and the line leaves them out.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .. import hostspans
from ..reduce import Run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def run_dir_of(run: Run) -> str | None:
    """The directory ``run.py`` gave this run's cell."""
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cells = json.load(f)["workloads"]
    except (OSError, ValueError, KeyError):
        return None
    for cell in cells:
        if (
            cell.get("config") == run.config.get("name")
            and cell.get("traffic") == run.traffic.get("name")
        ):
            return os.path.join(ROOT, "chiprun_out", "chipbench", cell["name"])
    return None


def _read(run: Run) -> dict | None:
    run_dir = run_dir_of(run)
    if run_dir is None or not os.path.isdir(os.path.join(run_dir, "trace")):
        return None
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "hostspans.py"),
             os.path.join(run_dir, "trace")],
            capture_output=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=300,
        )
        if out.returncode != 0:
            sys.stderr.write(out.stderr.decode("utf-8", "replace")[-2000:])
            return None
        events = json.loads(out.stdout)
        reduced = hostspans.reduce(events)
        if reduced is not None:
            with open(os.path.join(run_dir, "host_events.json"), "w") as f:
                json.dump(events, f)
            with open(os.path.join(run_dir, "host_breakdown.json"), "w") as f:
                json.dump(reduced, f, indent=1)
        return reduced
    except (OSError, ValueError, subprocess.SubprocessError) as e:
        sys.stderr.write(f"chipbench: host spans not read: {e}\n")
        return None


def reduced(run: Run) -> dict | None:
    """The reduction of this run's host spans, read once."""
    if not hasattr(run, "_host_spans"):
        run._host_spans = _read(run)
    return run._host_spans


def _layer(run: Run, layer: str):
    r = reduced(run)
    return r["layer_ms_per_round"].get(layer) if r else None


def core_ms_per_round(run: Run):
    """Self time of ``core.*`` and ``proposer.*`` on the loop thread,
    all nodes, over the rounds begun in the traced window."""
    return _layer(run, "consensus")


def network_ms_per_round(run: Run):
    return _layer(run, "network")


def store_ms_per_round(run: Run):
    return _layer(run, "store")


def ingest_ms_per_round(run: Run):
    return _layer(run, "ingest")


def verify_loop_ms_per_round(run: Run):
    """The verify service's spans on the loop thread: submit, collect,
    route, pack, spawn, deliver."""
    return _layer(run, "verify")


def loop_idle_share(run: Run):
    r = reduced(run)
    return r["loop_idle_share"] if r else None


def loop_unspanned_share(run: Run):
    """Busy loop time that no span covers, over busy time."""
    r = reduced(run)
    return r["loop_unspanned_share"] if r else None


def _wave(run: Run, key: str):
    r = reduced(run)
    return r["wave_ms"][key] if r else None


def wave_e2e_ms(run: Run):
    """First ``verify.submit`` of a wave to the end of its
    ``verify.deliver``, mean over the waves whole inside the trace."""
    return _wave(run, "e2e")


def wave_coalesce_ms(run: Run):
    return _wave(run, "coalesce")


def wave_staging_ms(run: Run):
    """``stage.pack`` + ``flatten`` + ``prepare``."""
    return _wave(run, "staging")


def wave_device_call_ms(run: Run):
    """``dispatch`` + ``device.execute`` + ``readback``."""
    return _wave(run, "device_call")


def wave_handoff_ms(run: Run):
    """Loop to slot thread and slot thread to delivery: the distances
    between the wave's spans on either side."""
    return _wave(run, "handoff")


def idle_wave_in_flight_share(run: Run):
    """Device-idle time during which some wave was open."""
    r = reduced(run)
    return r["idle_wave_in_flight_share"] if r else None


def idle_loop_busy_share(run: Run):
    """Device-idle time with no wave open and the loop inside a span."""
    r = reduced(run)
    return r["idle_loop_busy_share"] if r else None
