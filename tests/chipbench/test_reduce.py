"""The reducer on a small recorded log.

``data/committee_log.txt`` holds the Created, Committed and Timeout
lines of a ``--dry`` committee of 4 nodes (10 tx/s, 1 s ramp, 3 s
window, the drain), ``data/recorded.json`` what the generator knew.
``data/verify_lines.txt`` holds a ``warm in`` line and three ``Verify
service stats`` lines as the program printed them on the v5e (the 64-node
committee, 2026-09-27), their stamps moved into this run by the test.
"""

import calendar
import json
import os
import re
import time

import pytest

from chipbench import check
from chipbench.gen import Plan
from chipbench.logs import CommitteeLog
from chipbench.readers import client, consensus, gen, ingest, setup, verifier, verify
from chipbench.reduce import Run, percentile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(DATA, "recorded.json")) as f:
    REC = json.load(f)
with open(os.path.join(DATA, "committee_log.txt")) as f:
    TEXT = f.read()
with open(os.path.join(DATA, "verify_lines.txt")) as f:
    VERIFY_TEXT = f.read()
CONFIG = {"nodes": REC["nodes"], "guarantees": {"quorum": REC["quorum"]}}


def stamp(text: str) -> float:
    second, millis = text.split(".")
    return calendar.timegm(time.strptime(second, "%Y-%m-%dT%H:%M:%S")) + int(
        millis
    ) / 1000.0


def make_run(text: str = TEXT, refused=()) -> Run:
    plan = Plan(REC["traffic"], REC["nodes"], REC["seed"], REC["seconds"])
    log = CommitteeLog()
    log.feed(text)
    return Run(
        CONFIG, REC["traffic"], plan, log, REC["sent_at"], set(refused),
        REC["t_ramp"], t_end=REC["t_end"],
    )


def naive_commit(pid: str, quorum: int):
    """Straight from the text: the block whose Created line lists the
    payload, then the ``quorum``-th Committed line of that block."""
    (block,) = re.findall(
        r"Created block \d+ \(payloads \S*" + re.escape(pid) + r"\S*\) -> (\S+)",
        TEXT,
    )
    stamps = sorted(
        stamp(s)
        for s in re.findall(
            r"(\S+)Z \[INFO\] \S+ Committed block \d+ -> " + re.escape(block),
            TEXT,
        )
    )
    return stamps[quorum - 1]


def test_window_accounting():
    run = make_run()
    assert run.plan.window() == range(10, 40)  # 1 s ramp, 3 s at 10 tx/s
    assert run.t0 == REC["t_ramp"] + 1 and run.t1 == run.t0 + 3
    assert run.plan.count == 60  # the schedule goes on through the drain
    assert run.due(10) == pytest.approx(run.t0)
    assert len(run.window_latencies_ms()) == 30


@pytest.mark.parametrize("k", [10, 17, 39])
def test_latency_is_due_time_to_the_quorums_commit(k):
    run = make_run()
    expected = naive_commit(run.plan.ids[k], 3)
    assert run.commit_at(k) == pytest.approx(expected, abs=1e-6)
    # the third node's commit, not the first one's
    assert run.log.payload_commit(run.plan.ids[k], 1) <= run.commit_at(k)
    assert run.log.payload_commit(run.plan.ids[k], 4) >= run.commit_at(k)
    latency = run.window_latencies_ms()[k - 10]
    assert latency == pytest.approx(
        (expected - (REC["t_ramp"] + k / 10)) * 1e3, abs=1e-3
    )
    # from when it was due, so never less than from when it was sent
    assert latency >= (expected - REC["sent_at"][k]) * 1e3 - 1e-6


def test_percentiles_and_end_to_end_readers():
    run = make_run()
    lat = sorted(run.window_latencies_ms())
    assert percentile(lat, 0.50) == lat[14]
    assert percentile(lat, 0.95) == lat[28]
    p50 = client.commit_latency_p50_ms(run)
    # interpolated inside the millisecond the stamp was cut to
    assert lat[14] <= p50 <= lat[14] + 1.0
    assert lat[28] <= client.commit_latency_p95_ms(run) <= lat[28] + 1.0
    assert 0 < p50 < 1000
    assert ingest.refused_share(run) == 0.0
    assert check.violations(run.log, 4) == []


def test_binned_percentile_interpolates_inside_the_bin():
    # 10 values on a 1 ms grid: 4 in the bin at 5.0, 6 in the bin at 6.0
    values = [5.0] * 4 + [6.0] * 6
    assert percentile(values, 0.5) == 6.0
    # rank 5 of 10: one of the six in [6, 7) lies below it
    assert percentile(values, 0.5, 1.0) == pytest.approx(6.0 + 1 / 6)
    assert percentile(values, 0.4, 1.0) == pytest.approx(6.0)
    assert percentile([], 0.5) is None


def test_uncommitted_payloads_lie_beyond_the_tail():
    """Cut the log before the last payloads' commits: they stay in the
    sample, as the longest latency the run could have seen."""
    run = make_run()
    cut_at = run.t1 - 0.55
    kept = "".join(
        line for line in TEXT.splitlines(True)
        if stamp(line.split("Z ", 1)[0]) < cut_at
    )
    cut = make_run(kept)
    lat = cut.window_latencies_ms()
    missing = [k for k in cut.plan.window() if cut.commit_at(k) is None]
    assert 3 <= len(missing) <= 8
    beyond = (cut.t_end - cut.t0) * 1e3
    assert sorted(lat)[-len(missing):] == [beyond] * len(missing)
    assert max(x for x in lat if x != beyond) < beyond
    assert client.commit_latency_p95_ms(cut) >= beyond
    assert client.commit_latency_p50_ms(cut) < 1000
    # and a refused payload is one of them
    refused = make_run(refused=[12])
    assert refused.commit_at(12) is None
    assert ingest.refused_share(refused) == pytest.approx(100 / 30)


@pytest.mark.parametrize("node", ["first", "last"])
def test_a_payload_short_of_a_quorum_is_not_committed(node):
    """Take one node's Committed lines away: 3 of 4 still make the
    quorum; take two away and nothing is committed on one."""
    names = sorted(make_run().log.chain)
    gone = names[:1] if node == "first" else names[-1:]
    def without(nodes):
        return "".join(
            line for line in TEXT.splitlines(True)
            if not ("Committed block" in line
                    and any(f".{n} " in line for n in nodes))
        )
    three = make_run(without(gone))
    assert all(three.commit_at(k) is not None for k in three.plan.window())
    two = make_run(without(gone + names[1:2]))
    assert all(two.commit_at(k) is None for k in two.plan.window())
    assert check.violations(two.log, 4)  # two nodes committed nothing


def test_consensus_readers():
    run = make_run()
    made = sorted(
        stamp(s)
        for s in re.findall(r"(\S+)Z \[INFO\] \S+ Created block", TEXT)
        if run.t0 <= stamp(s) < run.t1
    )
    assert len(run.blocks_in_window()) == len(made) > 30
    assert consensus.round_ms(run) == pytest.approx(
        (made[-1] - made[0]) * 1e3 / (len(made) - 1)
    )
    assert 0 < consensus.payloads_per_block(run) <= 1.0
    assert 0 < consensus.propose_to_commit_ms(run) < 200
    assert consensus.view_changes(run) == 0.0
    late = gen.late_ms_p95(run)
    assert 0 <= late <= gen.late_ms_max(run) < 50


def test_a_stall_shows_whole_in_the_longest_round():
    """Take the blocks of one second of the window out of the log: the
    mean round hardly moves, the longest one is the stall."""
    run = make_run()
    made = sorted(b[0] for b in run.blocks_in_window())
    assert consensus.round_max_ms(run) == pytest.approx(
        max(b - a for a, b in zip(made, made[1:])) * 1e3
    )
    stalled = make_run("".join(
        line for line in TEXT.splitlines(True)
        if not run.t0 + 1 <= stamp(line.split("Z ", 1)[0]) < run.t0 + 2
    ))
    assert 1000 <= consensus.round_max_ms(stalled) < 1200
    assert consensus.round_ms(stalled) < 2 * consensus.round_ms(run)


def test_view_changes_count_rounds_not_lines():
    run = make_run()
    when = time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(run.t0 + 1))
    lines = "".join(
        f"{when}.000Z [WARNING] hotstuff_tpu.consensus.core.node{i} "
        f"Timeout reached for round {rnd}\n"
        for i in range(4) for rnd in (77, 78)
    )
    early = lines.replace(when, time.strftime(
        "%Y-%m-%dT%H:%M:%S", time.gmtime(run.t0 - 5)
    )).replace("round 7", "round 1")
    assert consensus.view_changes(make_run(TEXT + lines + early)) == 2.0


def test_stats_lines_are_read_as_deltas_over_the_window():
    run = make_run()
    assert verify.device_sig_share(run) is None  # nothing printed: no metric
    # the warm line and the first stats line before the window, the
    # second inside it, the third after the end of the run
    places = [run.t0 - 2, run.t0 - 0.5, run.t0 + 2, run.t_end + 1]
    moved = "".join(
        time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(at))
        + ".000Z" + line.split("Z", 1)[1]
        for at, line in zip(places, VERIFY_TEXT.splitlines(True))
    )
    run = make_run(TEXT + moved)
    # the line before the window: 42 dispatches, 2,204 signatures; the
    # last one before the end: 110 and 5,842 (the third is after it)
    assert run.log.stats_at(run.t0)["dispatches"] == 42
    assert run.log.stats_at(run.t_end)["dispatches"] == 110
    assert verify.device_sig_share(run) == 100.0
    assert verify.sigs_per_wave(run) == pytest.approx((5842 - 2204) / 68)
    assert verify.deadline_miss_share(run) == 0.0
    assert verifier.warmup_s(run) == 70.4
    assert verifier.cache_hits(run) == pytest.approx(100 / 3)
    run.setup = {"first_commit_s": 80.0, "child_started_s": 0.5, "setup_s": 81.0}
    assert setup.boot_s(run) == pytest.approx(80.0 - 0.5 - 70.4)
    assert setup.setup_s(run) == 81.0


def test_check_finds_what_breaks_the_guarantees():
    run = make_run()
    line = re.search(r".*Committed block 30 -> (\S+)\n", TEXT)
    forked = TEXT.replace(line.group(0), line.group(0).replace(
        line.group(1), "AAAAAAAAAAAAAAAA"
    ), 1)
    found = check.violations(make_run(forked).log, 4)
    assert any("round 30" in v for v in found)
    # one node silent: not every node can be read back
    node = re.search(r"core\.(\S+) Committed block 30 ", TEXT).group(1)
    silent = "".join(
        l for l in TEXT.splitlines(True) if f"core.{node} Committed" not in l
    )
    found = check.violations(make_run(silent).log, 4)
    assert any("3 of 4 nodes" in v for v in found)
    # a payload carried by two committed blocks
    first, second = re.findall(r"\(payloads (\S+)\) -> ", TEXT)[5:7]
    twice = TEXT.replace(f"(payloads {second})", f"(payloads {first})", 1)
    found = check.violations(make_run(twice).log, 4)
    assert any("committed in 2 blocks" in v for v in found)
    # a node that commits a height out of order
    line = re.search(r".*Committed block 30 -> \S+\n", TEXT).group(0)
    found = check.violations(make_run(TEXT + line).log, 4)
    assert any("after round" in v for v in found)


def test_log_is_read_as_it_grows(tmp_path):
    path = tmp_path / "node.log"
    half = len(TEXT) // 2
    log = CommitteeLog()
    path.write_text(TEXT[:half])
    log.poll(str(path))
    first = len(log.commits)
    path.write_text(TEXT)
    log.poll(str(path))
    whole = CommitteeLog()
    whole.feed(TEXT)
    assert 0 < first < len(log.commits) == len(whole.commits)
    assert log.created == whole.created and log.chain == whole.chain
