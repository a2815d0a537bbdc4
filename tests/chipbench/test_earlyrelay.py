"""``ingest.next_block_share`` from the ``Proposer stats:`` lines of a
canned committee log: two nodes, a line each every 10 s, numbers small
enough to work out by hand."""

import pytest

from chipbench.readers import earlyrelay, proposerstats

from .test_manifest import load
from .test_nodedup_cell import entry

HEAD = "2026-10-03T12:00:{s}.000Z [INFO] hotstuff_tpu.consensus.proposer.{node} Proposer stats: relayed={r} relay_frames={r} proposed_relayed=9 proposed_home=1 wait_ms_sum=100.0"


def _log(rows, counters=True) -> str:
    """``rows``: (second, node, wait_n, early_frames, carried_next)."""
    out = []
    for s, node, wait_n, early, nxt in rows:
        line = HEAD.format(s=s, node=node, r=wait_n) + f" wait_n={wait_n}"
        if counters:
            line += f" early_frames={early} carried_next={nxt}"
        out.append(line)
    return "\n".join(out) + "\n"


ROWS = [
    ("00", "aaaaaaaa", 10, 18, 2),
    ("00", "bbbbbbbb", 0, 0, 0),
    ("10", "aaaaaaaa", 30, 56, 12),
    ("10", "bbbbbbbb", 20, 40, 5),
    ("20", "aaaaaaaa", 50, 96, 20),
    ("20", "bbbbbbbb", 40, 76, 17),
    ("30", "aaaaaaaa", 999, 999, 999),
]
LOG = _log(ROWS)


class FakeRun:
    """What the readers touch of a ``reduce.Run``."""

    def __init__(self, text: str, after_first_s: float, seconds: float):
        self._proposer_stats = proposerstats.lines_of(text)
        first = proposerstats.lines_of(LOG)["aaaaaaaa"][0][0]
        self.t0 = first + after_first_s
        self.t1 = self.t0 + seconds


@pytest.mark.parametrize(
    "after_first_s, seconds, carried_next, wait_n",
    [
        # each node: the line of :20 less the line of :00
        (5.0, 20.0, (20 - 2) + (17 - 0), (50 - 10) + (40 - 0)),
        # each node: the line of :20 less the line of :10
        (12.0, 10.0, (20 - 12) + (17 - 5), (50 - 30) + (40 - 20)),
    ],
    ids=["two-lines-apart", "one-line-apart"],
)
def test_carried_next_over_first_carried_of_the_window(
    after_first_s, seconds, carried_next, wait_n
):
    run = FakeRun(LOG, after_first_s, seconds)
    assert earlyrelay.next_block_share(run) == pytest.approx(
        100.0 * carried_next / wait_n
    )
    # the frames are on the line for whoever wants them
    assert proposerstats.window_sum(run)["early_frames"] > 0


def test_no_payload_in_the_next_block_reads_zero_not_none():
    rows = [(s, node, wait_n, early, 0) for s, node, wait_n, early, _ in ROWS]
    assert earlyrelay.next_block_share(FakeRun(_log(rows), 5.0, 20.0)) == 0.0


@pytest.mark.parametrize(
    "text, after_first_s, seconds",
    [
        (_log(ROWS, counters=False), 5.0, 20.0),  # a parent commit's line
        # no block carried a payload in the window
        (_log([(s, n, 7, e, 3) for s, n, _, e, _ in ROWS]), 5.0, 20.0),
        ("", 5.0, 20.0),  # no line at all
        (LOG.splitlines()[0] + "\n", 5.0, 20.0),  # one line is no difference
        (LOG, 12.0, 5.0),  # no line printed between start and end
    ],
    ids=["parent", "nothing-carried", "empty", "one-line", "no-line-in-window"],
)
def test_nothing_to_read_is_none_and_never_raises(text, after_first_s, seconds):
    assert earlyrelay.next_block_share(FakeRun(text, after_first_s, seconds)) is None


def test_a_run_without_a_directory_reads_nothing():
    class Bare:
        config = {"name": "no-such-config"}
        traffic = {"name": "no-such-mix"}
        t0, t1 = 0.0, 1.0

    assert earlyrelay.next_block_share(Bare()) is None


def test_the_metric_is_in_the_manifest_for_every_cell_that_relays():
    """Since PR 35: every committee relays at admission, so the three
    cells report it, and a later cell joins the list."""
    metric = entry("per_layer", "ingest.next_block_share")
    assert {"colo64.low", "colo64.nodedup.low", "wan50.low"} <= set(
        metric["workloads"]
    )
    assert (metric["unit"], metric["better"], metric["source"]) == (
        "%", "higher", "program_counter"
    )
    assert (metric["layer"], metric["moves"]) == (
        "ingest", "commit_latency_p50_ms"
    )
    assert load("layers", metric["name"] + ".json")["reader"] == (
        "earlyrelay:next_block_share"
    )
