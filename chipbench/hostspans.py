"""From the profiler's ``.xplane.pb`` to what the host was doing: the
program's own spans (``hotstuff_tpu/telemetry/spans.py`` enters each as
a ``TraceAnnotation`` while a profiler session is active) beside the
device's ``XLA Ops`` line, on the profiler's one clock.

Run as a program (``python chipbench/hostspans.py <trace dir>``, with
``JAX_PLATFORMS=cpu`` once the chip's holder has gone: reading a trace
imports jax) it writes the events as JSON::

    {"threads": [[[name, start_ns, duration_ns, {id: value}], ...], ...],
     "device": [[name, start_ns, duration_ns], ...]}

one list a host thread that holds any of the program's spans, and the
first device's operations.  ``reduce`` is plain arithmetic on those
events and is what the tests check on a recorded file.  A program with
no spans (a parent commit) gives no threads, and ``reduce`` returns
None: the readers then leave their metrics out.

The rules the arithmetic rests on (``docs/TELEMETRY.md``): a span on the
event-loop thread wraps one synchronous segment, so spans on a thread
nest and a layer's **self time** is its spans' time less what their
child spans cover; ``loop.idle`` is the loop's ``select`` with a
timeout, so the loop's busy time is the window less ``loop.idle``;
every span of a verify wave carries ``wave=<serial>``, so a wait between
threads or across an ``await`` is the distance between two spans of one
wave.
"""

from __future__ import annotations

import glob
import json
import os
import sys

OPS_LINE = "XLA Ops"
KERNEL = "verify_compressed"

#: span-name prefix -> layer of PERF.md section 3 (loop thread)
LAYER_OF_PREFIX = (
    ("core.", "consensus"),
    ("proposer.", "consensus"),
    ("net.", "network"),
    ("store.", "store"),
    ("ingest.", "ingest"),
    ("verify.", "verify"),
    ("route.", "verify"),
    ("stage.", "verify"),
    ("native.", "verify"),
    ("loop.", "loop"),
)
#: the stages a slot thread runs inside its ``dispatch.wall`` frame
SLOT_STAGES = (
    "dispatch.wall", "flatten", "prepare", "dispatch", "device.execute",
    "mesh.psum", "readback", "host.verify",
)
IDLE = "loop.idle"


def layer_of(name: str) -> str | None:
    for prefix, layer in LAYER_OF_PREFIX:
        if name.startswith(prefix):
            return layer
    return "slot" if name in SLOT_STAGES else None


def trace_events(trace_dir: str) -> dict:
    """The program's spans by host thread and the first device's
    operations, from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    )
    out = {"threads": [], "device": []}
    if not paths:
        return out
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [
                    [e.name, int(e.start_ns), int(e.duration_ns),
                     {k: v for k, v in e.stats
                      if isinstance(v, (int, float, str))}]
                    for e in line.events
                    if layer_of(e.name) is not None
                ]
                if events:
                    out["threads"].append(events)
        elif plane.name.startswith("/device:TPU:") and not out["device"]:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device"] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
    return out


# ---- arithmetic on events ------------------------------------------------


def self_times(events: list[list]) -> list[tuple[list, int]]:
    """``(event, self_ns)`` for the spans of ONE thread: a span's time
    less what the spans nested in it cover.  Events nest (the profiler's
    contract); one that does not is taken as a sibling."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out: list[list] = []  # [event, self_ns]
    stack: list[list] = []
    for event in ordered:
        start, end = event[1], event[1] + event[2]
        while stack and stack[-1][0][1] + stack[-1][0][2] <= start:
            stack.pop()
        if stack and end <= stack[-1][0][1] + stack[-1][0][2]:
            stack[-1][1] -= event[2]
        else:
            stack.clear()
        entry = [event, event[2]]
        out.append(entry)
        stack.append(entry)
    return [(event, max(0, own)) for event, own in out]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Disjoint, sorted intervals covering the same points."""
    out: list[list[int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Total length of the intersection of two disjoint sorted lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def subtract(
    a: list[tuple[int, int]], b: list[tuple[int, int]]
) -> list[tuple[int, int]]:
    """The points of ``a`` (disjoint, sorted) that are not in ``b``."""
    out = []
    j = 0
    for start, end in a:
        at = start
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def length(intervals: list[tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def clip(events: list[list], lo: int, hi: int) -> list[tuple[int, int]]:
    return [
        (max(e[1], lo), min(e[1] + e[2], hi))
        for e in events
        if e[1] < hi and e[1] + e[2] > lo
    ]


def most_covering(events: list[list], lo: int, hi: int) -> list | None:
    """``[name, seconds, ids]`` of the span that covers most of
    ``[lo, hi)``, innermost first among equals; None if none does."""
    best = None
    for name, start, duration, ids in events:
        covered = min(start + duration, hi) - max(start, lo)
        if covered > 0 and (
            best is None
            or covered > best[0]
            or (covered == best[0] and duration < best[1])
        ):
            best = (covered, duration, name, ids)
    if best is None:
        return None
    return [best[2], best[0] / 1e9, best[3]]


def layers_within(outer: list[list], lo: int, hi: int) -> dict[str, float]:
    """Seconds of ``[lo, hi)`` by the layer of the outermost span that
    covers them on the loop thread; ``between`` is what none covers."""
    out: dict[str, float] = {}
    covered = 0
    for name, start, duration, _ in outer:
        ns = min(start + duration, hi) - max(start, lo)
        if ns > 0:
            layer = layer_of(name)
            out[layer] = out.get(layer, 0.0) + ns / 1e9
            covered += ns
    out["between"] = (hi - lo - covered) / 1e9
    return out


def waves_of(loop: list[list], slots: list[list]) -> dict[int, dict]:
    """The spans of each verify wave, keyed by its serial."""
    waves: dict[int, dict] = {}
    for where, events in (("loop", loop), ("slot", slots)):
        for event in events:
            serial = event[3].get("wave")
            if serial is not None:
                waves.setdefault(int(serial), {}).setdefault(
                    where, []
                ).append(event)
    return waves


def wave_split(spans: dict) -> dict | None:
    """One wave's time from its first ``verify.submit`` to the end of
    its ``verify.deliver``, split into stages and derived waits (ns).
    None unless the wave is whole inside the trace."""
    loop = sorted(spans.get("loop", []), key=lambda e: e[1])
    slot = sorted(spans.get("slot", []), key=lambda e: e[1])
    by = {}
    for event in loop + slot:
        by.setdefault(event[0], []).append(event)
    needed = ("verify.submit", "verify.collect", "verify.spawn",
              "dispatch.wall", "verify.deliver")
    if any(name not in by for name in needed):
        return None
    submit = by["verify.submit"][0][1]
    collect = by["verify.collect"][0]
    spawn = by["verify.spawn"][-1]
    wall = by["dispatch.wall"][0]
    deliver = by["verify.deliver"][-1]
    total = lambda *names: sum(  # noqa: E731
        e[2] for name in names for e in by.get(name, [])
    )
    out = {
        "e2e": deliver[1] + deliver[2] - submit,
        "coalesce": collect[1] - submit,
        "staging": total("stage.pack", "flatten", "prepare"),
        "device_call": total("dispatch", "device.execute", "mesh.psum",
                             "readback"),
        "handoff": (wall[1] - (spawn[1] + spawn[2]))
        + (deliver[1] - (wall[1] + wall[2])),
        # on the loop between collection and the hand-off, and delivery
        "loop_other": total("verify.collect", "route.decide", "native.pack",
                            "verify.spawn", "verify.deliver"),
        "sigs": spawn[3].get("sigs"),
        "bucket": spawn[3].get("bucket"),
        "call": None,
    }
    if "dispatch" in by and "device.execute" in by:
        last = by["device.execute"][-1]
        out["call"] = (by["dispatch"][0][1], last[1] + last[2])
    out["accounted"] = (
        out["coalesce"] + out["staging"] + out["device_call"]
        + out["handoff"] + out["loop_other"]
    )
    return out


def reduce(events: dict) -> dict | None:
    """The host's side of a traced window: the round split by layer,
    the wave split by stage, the device's idle time split by what the
    host was doing, and the ten longest idle gaps named.  None if the
    trace holds none of the program's spans."""
    threads = events.get("threads") or []
    # the loop thread: the one with most spans that are not a slot
    # thread's stages (a loop that never waits has no loop.idle)
    on_loop = lambda t: sum(layer_of(e[0]) != "slot" for e in t)  # noqa: E731
    loop = max(threads, key=on_loop, default=None)
    if loop is None or not on_loop(loop):
        return None
    slots = [
        e for t in threads if t is not loop for e in t if e[0] in SLOT_STAGES
    ]
    lo = min(e[1] for e in loop)
    hi = max(e[1] + e[2] for e in loop)
    window = hi - lo
    idle = union(clip([e for e in loop if e[0] == IDLE], lo, hi))
    busy_ns = window - length(idle)

    # the round, by layer: self time on the loop thread
    layers: dict[str, int] = {}
    by_span: dict[str, list[int]] = {}
    for event, own in self_times([e for e in loop if e[0] != IDLE]):
        layer = layer_of(event[0])
        layers[layer] = layers.get(layer, 0) + own
        entry = by_span.setdefault(event[0], [0, 0])
        entry[0] += own
        entry[1] += 1
    spanned = union(clip([e for e in loop if e[0] != IDLE], lo, hi))
    rounds = {
        e[3]["round"] for e in loop
        if e[0] == "proposer.make" and "round" in e[3]
    } or {
        e[3]["round"] for e in loop
        if e[0] == "core.proposal" and "round" in e[3]
    }
    n_rounds = len(rounds)
    per_round = lambda ns: ns / 1e6 / n_rounds if n_rounds else None  # noqa: E731

    # the wave, by stage
    waves = waves_of(loop, slots)
    splits = {
        serial: split
        for serial, split in (
            (serial, wave_split(spans)) for serial, spans in waves.items()
        )
        if split is not None
    }
    mean = lambda key: (  # noqa: E731
        sum(s[key] for s in splits.values()) / len(splits) / 1e6
        if splits else None
    )

    # the device's idle time, by what the host was doing
    device = events.get("device") or []
    busy_dev = union(clip(device, lo, hi))
    idle_dev = subtract([(lo, hi)], busy_dev)
    open_waves = union([
        (
            min(e[1] for e in spans["loop"] if e[0] == "verify.submit"),
            max(e[1] + e[2] for e in spans["loop"]
                if e[0] == "verify.deliver"),
        )
        for spans in waves.values()
        if {"verify.submit", "verify.deliver"}
        <= {e[0] for e in spans.get("loop", [])}
    ])
    idle_total = length(idle_dev)
    in_flight = overlap(idle_dev, open_waves)
    loop_busy = overlap(subtract(idle_dev, open_waves), spanned)
    share = lambda ns, of: 100.0 * ns / of if of else None  # noqa: E731

    # the same clock: each kernel event of the window inside its wave's
    # device call (dispatch start to device.execute end) and inside its
    # wave's dispatch.wall frame; how long after dispatch began it began
    kernels = [
        e for e in device
        if e[0].lstrip("%").startswith(KERNEL) and lo <= e[1] < hi
    ]
    calls = sorted(s["call"] for s in splits.values() if s["call"])
    frames = [(e[1], e[1] + e[2]) for e in slots if e[0] == "dispatch.wall"]
    inside = sum(
        any(a <= start <= b for a, b in calls) for _, start, _ in kernels
    )
    in_frame = sum(
        any(a <= start <= b for a, b in frames) for _, start, _ in kernels
    )
    after = sorted(
        (start - a) / 1e3
        for _, start, _ in kernels
        for a, b in calls
        if a - 1_000_000 <= start <= b
    )
    gaps = sorted(
        ((b - a, a, b) for a, b in idle_dev), reverse=True
    )[:10]
    # a gap is named by the stage, not by the frame around the stages
    stages = [e for e in slots if e[0] != "dispatch.wall"]
    outermost, end = [], -1
    for event in sorted(loop, key=lambda e: (e[1], -e[2])):
        if event[1] >= end:
            outermost.append(event)
            end = event[1] + event[2]
    return {
        "window_s": window / 1e9,
        "rounds": n_rounds,
        "spans": sum(1 for t in threads for _ in t),
        "spans_per_round": (
            sum(1 for e in loop if e[0] != IDLE) / n_rounds
            if n_rounds else None
        ),
        "layer_ms_per_round": {
            layer: per_round(ns) for layer, ns in sorted(layers.items())
        },
        "span_self_ms_per_round": {
            name: [per_round(ns), count]
            for name, (ns, count) in sorted(
                by_span.items(), key=lambda kv: -kv[1][0]
            )
        },
        "loop_idle_share": share(length(idle), window),
        "loop_unspanned_share": share(busy_ns - length(spanned), busy_ns),
        "loop_busy_ms_per_round": per_round(busy_ns),
        "loop_unspanned_ms_per_round": per_round(busy_ns - length(spanned)),
        "loop_idle_ms_per_round": per_round(length(idle)),
        "waves": len(splits),
        "waves_seen": len(waves),
        "wave_ms": {
            key: mean(key)
            for key in ("e2e", "coalesce", "staging", "device_call",
                        "handoff", "loop_other", "accounted")
        },
        "device_idle_s": idle_total / 1e9,
        "idle_wave_in_flight_share": share(in_flight, idle_total),
        "idle_loop_busy_share": share(loop_busy, idle_total),
        "kernel_events": len(kernels),
        "kernel_events_inside_their_wave": inside,
        "kernel_events_inside_their_frame": in_frame,
        "kernel_start_after_dispatch_us": (
            [after[0], after[len(after) // 2], after[-1]] if after else None
        ),
        "idle_gaps": [
            {
                "seconds": ns / 1e9,
                "loop": most_covering(loop, a, b),
                "loop_by_layer": layers_within(outermost, a, b),
                "slot": most_covering(stages, a, b),
            }
            for ns, a, b in gaps
        ],
    }


if __name__ == "__main__":
    json.dump(trace_events(sys.argv[1]), sys.stdout)
