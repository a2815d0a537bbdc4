"""How often a node finds a block's parent among the blocks it keeps,
from the ``ancestor_hits=`` and ``ancestor_misses=`` counters of the
``Host stats:`` line (``hotstuff_tpu/telemetry/hoststats.py``, counted
in ``hotstuff_tpu/consensus/synchronizer.py`` ``get_parent_block``):
parent lookups answered from the synchronizer's kept blocks and those
that went on to the store, every node of the process, cumulative; the
genesis answer is neither.

The window's share is taken as ``hoststats`` takes its own: the last
line at or before the window's end less the last at or before its
start.  A program whose line has no such counters (a parent commit)
gives None, and so does a window with no lookup in it.
"""

from __future__ import annotations

from ..reduce import Run
from . import hoststats


def hit_share(run: Run):
    """Parent lookups of the window answered without a store read and a
    decode, as a share of all of them, all nodes, in percent."""
    d = hoststats.window_delta(hoststats._lines(run), run.t0, run.t1)
    if d is None or "ancestor_hits" not in d:
        return None
    lookups = d["ancestor_hits"] + d["ancestor_misses"]
    if not lookups:
        return None
    return 100.0 * d["ancestor_hits"] / lookups
