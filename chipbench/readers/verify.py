"""The verify service's own counters: its last ``Verify service stats``
line less the one at the window's start (it prints one every 5 s)."""

from ..reduce import Run


def _delta(run: Run):
    end = run.log.stats_at(run.t_end)
    if end is None:
        return None
    start = run.log.stats_at(run.t0) or {}
    return {k: v - start.get(k, 0.0) for k, v in end.items()}


def device_sig_share(run: Run):
    d = _delta(run)
    if d is None or d["device_sigs"] + d["cpu_sigs"] == 0:
        return None
    return 100.0 * d["device_sigs"] / (d["device_sigs"] + d["cpu_sigs"])


def sigs_per_wave(run: Run):
    d = _delta(run)
    if d is None or d["dispatches"] == 0:
        return None
    return (d["device_sigs"] + d["cpu_sigs"]) / d["dispatches"]


def deadline_miss_share(run: Run):
    d = _delta(run)
    if d is None or d["device"] == 0:
        return None
    return 100.0 * d["deadline_misses"] / d["device"]
