"""The producer path: what the nodes refused."""

from ..reduce import Run


def refused_share(run: Run):
    """Payloads due in the window that a node answered with BUSY, or
    whose connection was gone, over those due."""
    window = run.plan.window()
    return 100.0 * sum(k in run.refused for k in window) / len(window)
