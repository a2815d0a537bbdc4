"""From the profiler's ``.xplane.pb`` to what the event loop ran: the
``cb.*`` span the node's loop enters for every handle it runs while a
profiler session is active (``hotstuff_tpu/node/main.py``,
``telemetry/spans.py`` ``trace_callbacks``), beside the loop thread's
``loop.idle`` and layer spans and the first device's operations.

Run as a program (``python chipbench/loopcalls.py <trace dir>``, with
``JAX_PLATFORMS=cpu`` once the chip's holder has gone, as
``hostspans.py`` is) it writes the events as JSON::

    {"loop": [[name, start_ns, duration_ns, {id: value}], ...],
     "device": [[name, start_ns, duration_ns], ...]}

``loop`` is the one host thread that ``hostspans.reduce`` takes for the
loop (``loop_thread``: the most spans that are not a slot thread's
stages), with every span of it that ``hostspans.trace_events`` keeps and
its callbacks, each ``cb`` annotation named ``cb.<kind>`` by its
``kind`` stat (``name=<qualname>``); ids are kept only where a reader uses them
(``round``, ``name``).  ``chipbench/readers/loopcalls.py`` reduces
them.  A program without ``cb.*`` spans (a parent commit) gives a loop
without them, and the reduction None.
"""

from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from chipbench.hostspans import OPS_LINE, layer_of  # noqa: E402

#: the annotation the loop enters a callback as (``telemetry/spans.py``
#: ``CALLBACK``); its ``kind`` stat names the event ``cb.<kind>`` here
CALLBACK = "cb"
KEPT_IDS = ("round", "name")


def kept(name: str) -> bool:
    return name == CALLBACK or layer_of(name) is not None


def event_of(name: str, start_ns: int, duration_ns: int, stats) -> list:
    """One kept event as the reduction takes it: a callback named by its
    kind (``cb.task`` ...), ids only where a reader uses them."""
    stats = dict(stats)
    if name == CALLBACK:
        name = f"{CALLBACK}.{stats.get('kind')}"
    return [name, int(start_ns), int(duration_ns),
            {k: v for k, v in stats.items() if k in KEPT_IDS}]


def loop_thread(lines: list[list[list]]) -> list[list]:
    """``hostspans.reduce``'s rule for the loop thread: of the host
    threads' kept events, the list with most layer spans that are not a
    slot thread's stages, the first such on a tie; [] when none has any."""
    best, out = 0, []
    for events in lines:
        on_loop = sum(layer_of(e[0]) not in (None, "slot") for e in events)
        if on_loop > best:
            best, out = on_loop, events
    return out


def loop_events(trace_dir: str) -> dict:
    """The loop thread's spans and the first device's operations, from
    the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    )
    out = {"loop": [], "device": []}
    if not paths:
        return out
    lines = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            lines += [
                [event_of(e.name, e.start_ns, e.duration_ns, e.stats)
                 for e in line.events if kept(e.name)]
                for line in plane.lines
            ]
        elif plane.name.startswith("/device:TPU:") and not out["device"]:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device"] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
    out["loop"] = loop_thread(lines)
    return out


if __name__ == "__main__":
    json.dump(loop_events(sys.argv[1]), sys.stdout)
