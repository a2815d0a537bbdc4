"""Set-up, on the harness's own clock."""

from ..reduce import Run


def setup_s(run: Run):
    return run.setup["setup_s"]


def boot_s(run: Run):
    """Child start to the committee's first commit, less the verifier's
    warm-up: interpreter, imports, the chip, 64 nodes' boot."""
    warm = run.log.warm[0] if run.log.warm else 0.0
    return run.setup["first_commit_s"] - run.setup["child_started_s"] - warm
