"""Node-to-node connections opened while the committee serves, from the
``conn_opens=`` counter of the ``Host stats:`` line
(``hotstuff_tpu/telemetry/hoststats.py``; every sender of every node,
cumulative), over the window as ``hoststats`` takes it, per block made
between the two lines.  A committee whose connections stay up reads 0
once every peer has been reached; one whose pools are bounded
(``HOTSTUFF_MAX_PEER_CONNS``) reconnects every round.  A program whose
line has no such counter (a parent commit) gives None."""

from __future__ import annotations

from ..reduce import Run
from . import hoststats


def conn_opens_per_round(run: Run):
    lines = hoststats._lines(run)
    d = hoststats.window_delta(lines, run.t0, run.t1)
    if d is None or "conn_opens" not in d:
        return None
    s1 = max(s for s, _ in lines if s <= run.t1)
    s0 = s1 - d["wall_s"]
    blocks = sum(
        s0 < made <= s1 for made, _n, _r, _ids in run.log.created.values()
    )
    return d["conn_opens"] / blocks if blocks else None
