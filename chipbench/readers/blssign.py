"""How often a BLS committee's signatures were made in one native call
(``hs_bls_sign``) instead of in pure Python, from the ``native_signs=``
and ``signs=`` counters of the ``BLS stats:`` line
(``hotstuff_tpu/telemetry/blsstats.py``), over the window as
``readers/bls.py`` takes it (``hoststats.window_delta``).  A line from a
program without the counter has no ``native_signs=`` and gives None, as
does a log with no such line."""

from .bls import _log
from .hoststats import window_delta


def native_sign_share(run):
    """``native_signs`` over ``signs`` made in the window, %."""
    d = window_delta(_log(run)[0], run.t0, run.t1)
    if d is None or "native_signs" not in d or not d["signs"]:
        return None
    return 100.0 * d["native_signs"] / d["signs"]
