"""How much of the window the event-loop thread spent on a core, from
the ``loop_cpu_s=`` counter of the ``Host stats:`` line
(``hotstuff_tpu/telemetry/hoststats.py``: the loop thread's own CPU
clock, cumulative), taken as ``hoststats`` takes its own counters: the
last line at or before the window's end less the last at or before its
start.  The loop's busy wall time less this is time it was runnable but
off a core: waiting for the interpreter's lock, or not scheduled.  A
program whose line has no such counter (a parent commit) gives None.
"""

from __future__ import annotations

from ..reduce import Run
from . import hoststats


def loop_cpu_share(run: Run):
    """The loop thread's CPU seconds over the window's wall time: 100
    is the loop thread on a core for the whole window."""
    d = hoststats.window_delta(hoststats._lines(run), run.t0, run.t1)
    if d is None or "loop_cpu_s" not in d:
        return None
    return 100.0 * d["loop_cpu_s"] / d["wall_s"]
