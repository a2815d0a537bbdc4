"""The Pallas kernel, from the profiler's trace: the device durations
of the ``verify_compressed`` custom call."""

from ..reduce import Run


def wave_us(run: Run):
    if not run.trace or not run.trace["kernel_calls"]:
        return None
    return 1e6 * run.trace["kernel_s"] / run.trace["kernel_calls"]


def waves_per_s(run: Run):
    if not run.trace:
        return None
    return run.trace["kernel_calls"] / run.trace["window_s"]
