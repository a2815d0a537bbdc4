"""The digest relay's two metrics, read from the ``Proposer stats:``
lines of a canned committee log: two nodes, one line each every 10 s,
numbers small enough to work out by hand."""

import pytest

from chipbench.readers import proposerstats

LOG = """\
2026-10-01T12:00:00.000Z [INFO] hotstuff_tpu.consensus.proposer.aaaaaaaa Proposer stats: relayed=10 relay_frames=8 proposed_relayed=30 proposed_home=2 wait_ms_sum=4000.0 wait_n=10
2026-10-01T12:00:00.004Z [INFO] hotstuff_tpu.consensus.proposer.bbbbbbbb Proposer stats: relayed=0 relay_frames=0 proposed_relayed=0 proposed_home=0 wait_ms_sum=0.0 wait_n=0
2026-10-01T12:00:05.000Z [INFO] hotstuff_tpu.consensus.core.aaaaaaaa Committed block 7 -> xyz
2026-10-01T12:00:10.000Z [INFO] hotstuff_tpu.consensus.proposer.aaaaaaaa Proposer stats: relayed=30 relay_frames=25 proposed_relayed=60 proposed_home=4 wait_ms_sum=9000.0 wait_n=30
2026-10-01T12:00:10.004Z [INFO] hotstuff_tpu.consensus.proposer.bbbbbbbb Proposer stats: relayed=20 relay_frames=20 proposed_relayed=40 proposed_home=0 wait_ms_sum=5000.0 wait_n=20
2026-10-01T12:00:20.000Z [INFO] hotstuff_tpu.consensus.proposer.aaaaaaaa Proposer stats: relayed=50 relay_frames=45 proposed_relayed=100 proposed_home=8 wait_ms_sum=15000.0 wait_n=50
2026-10-01T12:00:20.004Z [INFO] hotstuff_tpu.consensus.proposer.bbbbbbbb Proposer stats: relayed=40 relay_frames=40 proposed_relayed=90 proposed_home=4 wait_ms_sum=12000.0 wait_n=45
2026-10-01T12:00:30.000Z [INFO] hotstuff_tpu.consensus.proposer.aaaaaaaa Proposer stats: relayed=999 relay_frames=999 proposed_relayed=999 proposed_home=999 wait_ms_sum=99999.0 wait_n=999
2026-10-01T12:00:30.004Z [INFO] hotstuff_tpu.consensus.proposer.bbbbbbbb Proposer stats: relayed=x relay_frames=
"""


class FakeRun:
    """What the readers touch of a ``reduce.Run``."""

    def __init__(self, text: str, t0: float, t1: float):
        self._proposer_stats = proposerstats.lines_of(text)
        self.t0, self.t1 = t0, t1


@pytest.fixture()
def run():
    first = proposerstats.lines_of(LOG)["aaaaaaaa"][0][0]
    # the window: 12:00:05 to 12:00:25
    return FakeRun(LOG, first + 5.0, first + 25.0)


def test_lines_are_kept_by_node_and_a_broken_one_is_skipped():
    lines = proposerstats.lines_of(LOG)
    assert sorted(lines) == ["aaaaaaaa", "bbbbbbbb"]
    assert [len(v) for v in lines.values()] == [4, 3]
    apart = lines["bbbbbbbb"][1][0] - lines["aaaaaaaa"][1][0]
    assert apart == pytest.approx(0.004, abs=1e-6)
    assert lines["aaaaaaaa"][2][1]["wait_n"] == 50.0


def test_a_nodes_window_is_its_last_line_less_its_first_summed_over_nodes(run):
    # each node: the line of 12:00:20 less the line of 12:00:00
    total = proposerstats.window_sum(run)
    assert total["proposed_relayed"] == (100 - 30) + (90 - 0)
    assert total["proposed_home"] == (8 - 2) + (4 - 0)
    assert total["relayed"] == 40 + 40 and total["relay_frames"] == 37 + 40


@pytest.mark.parametrize(
    "reader, expected",
    [
        # 160 of 170 payloads proposed by a node that is not their home
        (proposerstats.relay_hit_share, 100 * 160 / 170),
        # (15000 - 4000 + 12000) ms over (50 - 10 + 45) payloads
        (proposerstats.payload_wait_ms, 23000 / 85),
    ],
    ids=["relay_hit_share", "payload_wait_ms"],
)
def test_the_two_metrics_on_the_canned_log(run, reader, expected):
    assert reader(run) == pytest.approx(expected)


@pytest.mark.parametrize(
    "reader",
    [proposerstats.relay_hit_share, proposerstats.payload_wait_ms],
    ids=["relay_hit_share", "payload_wait_ms"],
)
@pytest.mark.parametrize(
    "text",
    [
        # a parent commit prints no such line
        "2026-10-01T12:00:05.000Z [INFO] x.aaaaaaaa Committed block 7 -> xyz\n",
        # one line a node is no difference
        LOG.splitlines()[0] + "\n",
        # nothing proposed, nothing seen in the window
        "".join(
            f"2026-10-01T12:00:{s}.000Z [INFO] p.aaaaaaaa Proposer stats: "
            "relayed=0 relay_frames=0 proposed_relayed=5 proposed_home=5 "
            "wait_ms_sum=10.0 wait_n=4\n"
            for s in ("00", "10", "20")
        ),
    ],
    ids=["no-line", "one-line", "nothing-moved"],
)
def test_nothing_to_read_gives_none_and_does_not_raise(reader, text):
    first = proposerstats.lines_of(LOG)["aaaaaaaa"][0][0]
    assert reader(FakeRun(text, first + 5.0, first + 25.0)) is None


def test_a_run_without_a_directory_reads_nothing():
    class Bare:
        config = {"name": "no-such-config"}
        traffic = {"name": "no-such-mix"}
        t0, t1 = 0.0, 1.0

    assert proposerstats.relay_hit_share(Bare()) is None
    assert proposerstats.payload_wait_ms(Bare()) is None
