"""From the profiler's ``.xplane.pb`` to busy time, operation totals and
idle gaps.

Run as a program (after the chip's holder has gone: reading a trace
imports jax) it writes the device's events as JSON; ``summarise`` is
plain arithmetic on those events and is what the tests check on a
recorded trace.
"""

from __future__ import annotations

import glob
import json
import os
import sys

#: the line of a device's plane that holds one event for each operation
OPS_LINE = "XLA Ops"
KERNEL = "verify_compressed"


def device_events(trace_dir: str) -> list[list]:
    """``[[name, start_ns, duration_ns], ...]`` for each device plane
    of the newest trace under ``trace_dir``, one list a device."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    )
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    devices = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices.append(
                    [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
                )
    return devices


def op_name(text: str) -> str:
    """``%copy-start.3 = (...) copy-start(...)`` -> ``copy-start.3``;
    a custom call keeps its kind: ``verify_compressed.1_custom-call``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if " custom-call(" in text:
        name += "_custom-call"
    return name


def summarise(events: list[list], window_s: float) -> dict:
    """Busy seconds (the union of the intervals in which an operation
    ran), seconds and calls by operation, and the idle gaps, each named
    by the operation that ended it."""
    busy_ns = 0
    totals: dict[str, list[float]] = {}
    gaps: list[tuple[int, str]] = []
    end = None
    for text, start, duration in sorted(events, key=lambda e: e[1]):
        name = op_name(text)
        entry = totals.setdefault(name, [0, 0])
        entry[0] += duration
        entry[1] += 1
        if end is None or start > end:
            if end is not None:
                gaps.append((start - end, name))
            busy_ns += duration
            end = start + duration
        elif start + duration > end:
            busy_ns += start + duration - end
            end = start + duration
    by_time = sorted(totals.items(), key=lambda kv: -kv[1][0])
    kernel = [v for k, v in totals.items() if k.startswith(KERNEL)]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_s,
        "device_ops": [[k, v[0] / 1e9] for k, v in by_time[:10]],
        "idle_gaps": [
            [f"ends_at_{name}", ns / 1e9]
            for ns, name in sorted(gaps, reverse=True)[:10]
        ],
        "kernel_s": sum(v[0] for v in kernel) / 1e9,
        "kernel_calls": sum(v[1] for v in kernel),
    }


def summarise_devices(devices: list[list], window_s: float) -> dict | None:
    """The summary of the first device, with ``busy_s`` averaged over
    all of them (a cell on four chips has four)."""
    if not devices:
        return None
    each = [summarise(events, window_s) for events in devices]
    out = each[0]
    out["busy_s"] = sum(s["busy_s"] for s in each) / len(each)
    return out


if __name__ == "__main__":
    import ending  # beside this file: a program here, not the package's

    # ``run.py`` waits for this reader; killed meanwhile, it is missed
    ending.die_with_parent()
    json.dump(device_events(sys.argv[1]), sys.stdout)
