"""How late the generator ran: a starved generator is not a fast
committee."""

from ..reduce import Run, percentile


def _late_ms(run: Run):
    return [
        (run.sent_at[k] - run.due(k)) * 1e3
        for k in run.plan.window()
        if run.sent_at[k] is not None
    ]


def late_ms_p95(run: Run):
    return percentile(_late_ms(run), 0.95)


def late_ms_max(run: Run):
    """A stall of the generator itself (a full socket, a long poll of
    the log) shows here whole."""
    return max(_late_ms(run), default=None)
