"""What the injected link delays came to and what they made the
synchronizers do, from the ``wan_frames=``, ``wan_delay_ms=`` and
``sync_requests=`` counters of the ``Host stats:`` line
(``hotstuff_tpu/telemetry/hoststats.py``): frames a sender's
``LinkScheduler`` held and the sum of what it held them for
(``hotstuff_tpu/network/wan.py``), and parent requests sent to peers
(``hotstuff_tpu/consensus/synchronizer.py``), every node of the process,
cumulative.

The window's share is taken as ``hoststats`` takes its own: the last
line at or before the window's end less the last at or before its
start.  A program whose line has no such counters (a parent commit)
gives None, and so does a window in which no frame was held.
"""

from __future__ import annotations

from ..reduce import Run
from . import hoststats


def _delta(run: Run, counter: str):
    d = hoststats.window_delta(hoststats._lines(run), run.t0, run.t1)
    return None if d is None or counter not in d else d


def delay_ms(run: Run):
    """Mean time a node->node frame of the window was held for its
    link's one-way delay, in ms: the matrix's expectation over the
    links the frames took."""
    d = _delta(run, "wan_frames")
    if d is None or not d["wan_frames"]:
        return None
    return d["wan_delay_ms"] / d["wan_frames"]


def sync_requests(run: Run):
    """Parent requests sent in the window, all nodes."""
    d = _delta(run, "sync_requests")
    return None if d is None else d["sync_requests"]
