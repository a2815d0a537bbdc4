"""What the event loop ran between the layer spans: the ``cb.*`` span
the node's loop enters for every handle while the profiler is on
(``chipbench/loopcalls.py`` extracts them with the loop thread's
``loop.idle`` and layer spans and the first device's operations).

The busy loop time no layer span covers (``hostspans.reduce``'s
``loop_unspanned_ms_per_round``) is split into five parts that sum to
it: the **self time** of each callback kind (its spans' time less the
layer spans and ``loop.idle`` inside them) and the **machinery**, busy
time inside no ``cb.*`` span at all (``_run_once`` itself: the timer
heap, the ready queue, the events after ``select`` returns).  Rounds
are counted as ``hostspans.reduce`` counts them.  The reader writes
``loop_breakdown.json`` beside ``host_breakdown.json``: the callbacks
that take the most self time a round, and what the loop ran during
the ten longest device-idle gaps.  An untraced run, or a program
without ``cb.*`` spans (a parent commit), gives None for every metric
here, and the line leaves them out.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .. import hostspans
from ..hostspans import clip, length, overlap, subtract, union
from ..reduce import Run
from .hostspans import HERE, run_dir_of

KINDS = ("cb.task", "cb.io", "cb.timer", "cb.call")
TOP = 20


def _uncovered(events: list[list], cover: list[tuple[int, int]]) -> list[int]:
    """For each of ``events`` (sorted by start, not overlapping each
    other) the nanoseconds that ``cover`` (disjoint, sorted) leaves."""
    out, j = [], 0
    for _, start, duration, _ in events:
        end = start + duration
        while j < len(cover) and cover[j][1] <= start:
            j += 1
        covered, k = 0, j
        while k < len(cover) and cover[k][0] < end:
            covered += min(end, cover[k][1]) - max(start, cover[k][0])
            k += 1
        out.append(duration - covered)
    return out


def _gap(calls, outermost, idle, blocked, a: int, b: int) -> dict:
    """One device-idle gap ``[a, b)``: the callback that covers most of
    it, and its milliseconds by callback kind (self time), by layer (the
    outermost layer span), ``loop.idle`` and machinery: a partition."""
    best = hostspans.most_covering(calls, a, b)
    inside = [e for e in calls if e[1] < b and e[1] + e[2] > a]
    clipped = [
        [e[0], max(e[1], a), min(e[1] + e[2], b) - max(e[1], a), e[3]]
        for e in inside
    ]
    by_kind: dict[str, float] = {}
    for event, own in zip(clipped, _uncovered(clipped, blocked)):
        by_kind[event[0]] = by_kind.get(event[0], 0.0) + own / 1e6
    by_layer = hostspans.layers_within(outermost, a, b)
    by_layer.pop("between")
    window = [(a, b)]
    return {
        "ms": (b - a) / 1e6,
        "callback": (
            {"kind": best[0], "name": best[2].get("name"),
             "ms": best[1] * 1e3}
            if best else None
        ),
        "by_kind_ms": by_kind,
        "by_layer_ms": {k: v * 1e3 for k, v in by_layer.items()},
        "idle_ms": overlap(window, idle) / 1e6,
        "machinery_ms": length(
            subtract(subtract(window, blocked), union(clip(calls, a, b)))
        ) / 1e6,
    }


def reduce(events: dict) -> dict | None:
    """The loop's busy time between the layer spans, by callback kind
    and machinery, a round; None without ``cb.*`` spans or a round."""
    loop = events.get("loop") or []
    layer = [e for e in loop if hostspans.layer_of(e[0]) is not None]
    if not layer or not any(e[0] in KINDS for e in loop):
        return None
    # hostspans.reduce's window: the loop thread's layer spans
    lo = min(e[1] for e in layer)
    hi = max(e[1] + e[2] for e in layer)
    calls = sorted(
        (
            [e[0], max(e[1], lo), min(e[1] + e[2], hi) - max(e[1], lo), e[3]]
            for e in loop
            if e[0] in KINDS and e[1] < hi and e[1] + e[2] > lo
        ),
        key=lambda e: e[1],
    )
    idle = union(clip([e for e in layer if e[0] == hostspans.IDLE], lo, hi))
    spans = [e for e in layer if e[0] != hostspans.IDLE]
    spanned = union(clip(spans, lo, hi))
    blocked = union(idle + spanned)
    in_calls = union([(e[1], e[1] + e[2]) for e in calls])
    busy = subtract([(lo, hi)], idle)
    rounds = {
        e[3]["round"] for e in layer
        if e[0] == "proposer.make" and "round" in e[3]
    } or {
        e[3]["round"] for e in layer
        if e[0] == "core.proposal" and "round" in e[3]
    }
    if not rounds:
        return None
    per_round = lambda ns: ns / 1e6 / len(rounds)  # noqa: E731

    kinds = dict.fromkeys(KINDS, 0)
    by_name: dict[tuple[str, str], list[int]] = {}
    for event, own in zip(calls, _uncovered(calls, blocked)):
        kinds[event[0]] += own
        name = (event[0], event[3].get("name", ""))
        entry = by_name.setdefault(name, [0, 0])
        entry[0] += own
        entry[1] += 1
    busy_ns = length(busy)
    machinery = length(subtract(subtract(busy, spanned), in_calls))
    outside = length(subtract(spanned, in_calls))

    device = events.get("device") or []
    idle_dev = subtract([(lo, hi)], union(clip(device, lo, hi)))
    gaps = sorted(((b - a, a, b) for a, b in idle_dev), reverse=True)[:10]
    outermost, end = [], -1
    for event in sorted(spans, key=lambda e: (e[1], -e[2])):
        if event[1] >= end:
            outermost.append(event)
            end = event[1] + event[2]
    return {
        "window_s": (hi - lo) / 1e9,
        "rounds": len(rounds),
        "callbacks": len(calls),
        "callbacks_per_round": len(calls) / len(rounds),
        "kind_ms_per_round": {k: per_round(ns) for k, ns in kinds.items()},
        "machinery_ms_per_round": per_round(machinery),
        "unspanned_ms_per_round": per_round(sum(kinds.values()) + machinery),
        "loop_busy_ms_per_round": per_round(busy_ns),
        "spans_outside_callbacks_ms_per_round": per_round(outside),
        "spans_outside_callbacks_share": (
            100.0 * outside / busy_ns if busy_ns else None
        ),
        "top": [
            {"kind": kind, "name": name,
             "self_ms_per_round": per_round(ns), "count": count}
            for (kind, name), (ns, count) in sorted(
                by_name.items(), key=lambda kv: -kv[1][0]
            )[:TOP]
        ],
        "idle_gaps": [
            _gap(calls, outermost, idle, blocked, a, b)
            for _, a, b in gaps
        ],
    }


def _read(run: Run) -> dict | None:
    run_dir = run_dir_of(run)
    if run_dir is None or not os.path.isdir(os.path.join(run_dir, "trace")):
        return None
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "loopcalls.py"),
             os.path.join(run_dir, "trace")],
            capture_output=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=300,
        )
        if out.returncode != 0:
            sys.stderr.write(out.stderr.decode("utf-8", "replace")[-2000:])
            return None
        events = json.loads(out.stdout)
        reduced = reduce(events)
        if reduced is not None:
            with open(os.path.join(run_dir, "loop_breakdown.json"), "w") as f:
                json.dump(reduced, f, indent=1)
        return reduced
    except (OSError, ValueError, subprocess.SubprocessError) as e:
        sys.stderr.write(f"chipbench: loop callbacks not read: {e}\n")
        return None


def reduced(run: Run) -> dict | None:
    """The reduction of this run's loop callbacks, read once."""
    if not hasattr(run, "_loop_calls"):
        run._loop_calls = _read(run)
    return run._loop_calls


def _kind(run: Run, kind: str):
    r = reduced(run)
    return r["kind_ms_per_round"][kind] if r else None


def task_ms_per_round(run: Run):
    """Self time of ``cb.task`` (a Task's step or wake-up) on the loop
    thread, all nodes, over the rounds begun in the traced window."""
    return _kind(run, "cb.task")


def io_ms_per_round(run: Run):
    """Self time of ``cb.io``: the transports' reader and writer
    callbacks, the protocol's ``data_received`` in them."""
    return _kind(run, "cb.io")


def timer_ms_per_round(run: Run):
    return _kind(run, "cb.timer")


def call_ms_per_round(run: Run):
    """Self time of ``cb.call``: done-callbacks, threadsafe calls."""
    return _kind(run, "cb.call")


def machinery_ms_per_round(run: Run):
    """Busy loop time (the window less ``loop.idle``) inside no span at
    all: ``_run_once`` between the callbacks."""
    r = reduced(run)
    return r["machinery_ms_per_round"] if r else None


def callbacks_per_round(run: Run):
    r = reduced(run)
    return r["callbacks_per_round"] if r else None
