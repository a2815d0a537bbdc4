"""How far the verify plane's work fans out, from the counters the
``Verify service stats`` line gained with the ``colo64.nodedup``
deployment (ISSUE 28): ``submitted_sigs`` (what the cores handed in,
before collection), ``lanes`` (rows handed to the device, pads
included) and ``chunks`` (backend calls), last line less the one at the
window's start, as ``readers/verify.py`` reads the others.  A program
whose line lacks them (a parent commit) gives None for all three, and
the line leaves them out."""

from ..reduce import Run
from .verify import _delta


def _counters(run: Run, needed: str):
    """The window's counters, if the program prints ``needed``."""
    d = _delta(run)
    return d if d is not None and needed in d else None


def evaluated_share(run: Run):
    """Signatures evaluated over signatures submitted: 100 where every
    node's copy is verified for that node, about 100/fan-out where
    cross-node dedup serves all nodes from one evaluation."""
    d = _counters(run, "submitted_sigs")
    if d is None or d["submitted_sigs"] == 0:
        return None
    return 100.0 * (d["device_sigs"] + d["cpu_sigs"]) / d["submitted_sigs"]


def lane_fill_share(run: Run):
    """Device-routed signatures over the rows the device was handed:
    the occupancy of the buckets the waves were padded to."""
    d = _counters(run, "lanes")
    if d is None or d["lanes"] == 0:
        return None
    return 100.0 * d["device_sigs"] / d["lanes"]


def chunks_per_wave(run: Run):
    """Backend calls over device dispatches: 1 while every wave fits the
    largest bucket, 3 for a 2,816-signature wave cut at 1,024."""
    d = _counters(run, "chunks")
    if d is None or d["device"] == 0:
        return None
    return d["chunks"] / d["device"]
