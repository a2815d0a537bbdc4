"""The rounds, from the leaders' ``Created`` lines and every node's
``Committed`` and ``Timeout`` lines."""

from statistics import median

from ..reduce import Run


def round_ms(run: Run):
    """The mean gap between the blocks made in the window: the log's
    stamps are whole milliseconds, and a median of 12 ms gaps would
    move a twelfth at a time."""
    made = [b[0] for b in run.blocks_in_window()]
    if len(made) < 2:
        return None
    return (made[-1] - made[0]) * 1e3 / (len(made) - 1)


def round_max_ms(run: Run):
    """The longest gap between two blocks made in the window: a stall
    of the committee shows here whole, where a percentile dilutes it."""
    made = [b[0] for b in run.blocks_in_window()]
    if len(made) < 2:
        return None
    return max(b - a for a, b in zip(made, made[1:])) * 1e3


def propose_to_commit_ms(run: Run):
    """Median, over the blocks made in the window, from the leader's
    ``Created`` line to the commit on a quorum."""
    spans = []
    for made, block, _rnd, _ids in run.blocks_in_window():
        at = run.log.quorum_commit(block, run.quorum)
        if at is not None:
            spans.append((at - made) * 1e3)
    return median(spans) if spans else None


def payloads_per_block(run: Run):
    blocks = run.blocks_in_window()
    if not blocks:
        return None
    return sum(len(b[3]) for b in blocks) / len(blocks)


def view_changes(run: Run):
    """Rounds that some node timed out of, from the window's start to
    the end of the drain."""
    return float(
        len({rnd for at, _node, rnd in run.log.timeouts if at >= run.t0})
    )
