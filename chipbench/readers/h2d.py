"""How many host arrays a backend call hands to the device, from the
``h2d=`` counter the ``Verify service stats`` line gained with ISSUE 38
(``hotstuff_tpu/tpu/ed25519.py`` counts every host array it gives jax;
the service prints the sum, cumulative, beside ``chunks=``, its own
count of backend calls): last line less the one at the window's start,
as ``readers/verify.py`` reads the others.  1.0 is a wave that reaches
the chip as one buffer; the five arrays of the code before it would
read 5.  A program whose line has no such counter (a parent commit)
gives None, and so does a window without a backend call.
"""

from ..reduce import Run
from .fanout import _counters


def h2d_per_call(run: Run):
    """Host arrays handed to the device over backend calls (chunks)."""
    d = _counters(run, "h2d")
    if d is None or not d.get("chunks"):
        return None
    return d["h2d"] / d["chunks"]
