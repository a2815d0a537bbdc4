"""How often the relay at admission puts a payload into the very next
block, from the ``early_frames=`` and ``carried_next=`` counters of the
``Proposer stats:`` line (``hotstuff_tpu/consensus/proposer.py``): relay
frames a home sent the moment it admitted a digest, and this home's
payloads whose first carrying block is the one that was next to be made
when they were admitted.  Both are cumulative and a node's own, beside
``wait_n=`` (payloads of this home a processed block has carried).

The window's counters are taken as ``proposerstats`` takes its own
(every node's last line less its first, summed).  A program whose line
has no such counters (a parent commit) gives None, and so does a window
in which no block carried a payload of any home.
"""

from __future__ import annotations

from ..reduce import Run
from .proposerstats import window_sum


def next_block_share(run: Run):
    """Payloads first carried by the block that was the next to be made
    when their home admitted them, as a share of all payloads a block
    first carried in the window, all homes, in percent."""
    d = window_sum(run)
    if d is None or "carried_next" not in d or not d.get("wait_n"):
        return None
    return 100.0 * d["carried_next"] / d["wait_n"]
