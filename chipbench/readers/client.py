"""What the committee's clients see."""

from ..reduce import STAMP_MS, Run, percentile


def commit_latency_p50_ms(run: Run):
    return percentile(run.window_latencies_ms(), 0.50, STAMP_MS)


def commit_latency_p95_ms(run: Run):
    return percentile(run.window_latencies_ms(), 0.95, STAMP_MS)
