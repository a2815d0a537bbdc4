"""How far the store's write batch engages, from the ``store_appends=``
and ``store_records=`` counters of the ``Host stats:`` line
(``hotstuff_tpu/telemetry/hoststats.py``, counted by the engines'
adapters in ``hotstuff_tpu/store/``): writes of a WAL and the records
they carried, every node's engine of the process, cumulative.

The window's share is taken as ``hoststats`` takes its own: the last
line at or before the window's end less the last at or before its
start.  A program whose line has no such counters (a parent commit)
gives None.
"""

from __future__ import annotations

from ..reduce import Run
from . import hoststats


def records_per_append(run: Run):
    """Records written to the WALs in the window over the appends that
    carried them, all nodes: 1.0 is a write a record."""
    d = hoststats.window_delta(hoststats._lines(run), run.t0, run.t1)
    if d is None or not d.get("store_appends"):
        return None
    return d["store_records"] / d["store_appends"]
