"""How many votes a device call of the running sum carried, from the
``device_adds=`` and ``device_calls=`` counters of the ``BLS stats:``
line (``hotstuff_tpu/telemetry/blsstats.py``), over the window as
``readers/bls.py`` takes it (``hoststats.window_delta``).  A line from a
program without the counter has no ``device_calls=`` and gives None, as
does a log with no such line or a window with no call."""

from .bls import _log
from .hoststats import window_delta


def adds_per_call(run):
    """``device_adds`` over ``device_calls`` made in the window."""
    d = window_delta(_log(run)[0], run.t0, run.t1)
    if d is None or "device_calls" not in d or not d["device_calls"]:
        return None
    return d["device_adds"] / d["device_calls"]
