"""Deterministic simulation plane (hotstuff_tpu/sim, docs/SIM.md).

Covers the virtual-time loop, the determinism contract (same seed ⇒
byte-identical journal), seeded crash-point injection with torn-WAL
recovery, shrinker convergence on a planted safety bug, the committed
regression seed corpus (tests/data/sim_seeds.json), and the virtual-time
port of the crash-restart-under-partition e2e — everything here runs in
virtual time, so no ``slow`` marker anywhere in this file.
"""

from __future__ import annotations

import asyncio
import json
import os
import struct
import time

import pytest

from hotstuff_tpu.sim import (
    SimDeadlock,
    SimLoop,
    VirtualClock,
    draw_schedule,
    run_schedule,
    shrink,
)
from hotstuff_tpu.sim.harness import SimCluster
from hotstuff_tpu.sim.schedule import SCHEDULE_VERSION

CORPUS = os.path.join(os.path.dirname(__file__), "data", "sim_seeds.json")


# ---- virtual loop -----------------------------------------------------


def test_virtual_loop_sleeps_cost_no_wall_time():
    """An hour of virtual sleeping must finish in well under a second:
    the loop's clock jumps to the next timer whenever the run queue is
    empty."""
    loop = SimLoop()
    clock = VirtualClock(loop)

    async def nap():
        for _ in range(60):
            await asyncio.sleep(60.0)
        return clock.monotonic()

    t0 = time.monotonic()
    try:
        virtual = loop.run_until_complete(nap())
    finally:
        loop.close()
    assert virtual >= 3600.0
    assert time.monotonic() - t0 < 5.0


def test_virtual_loop_detects_deadlock():
    """A wait with no timer to jump to is a deadlock, not a hang."""
    loop = SimLoop()
    try:
        with pytest.raises(SimDeadlock):
            loop.run_until_complete(loop.create_future())
    finally:
        loop.close()


# ---- determinism contract ---------------------------------------------


def test_same_seed_byte_identical_journal(tmp_path):
    """The whole run — verdict fields AND the merged journal bytes — is
    a pure function of the schedule."""
    schedule = draw_schedule(3, nodes=4)
    a = run_schedule(schedule, workdir=str(tmp_path / "a"))
    b = run_schedule(schedule, workdir=str(tmp_path / "b"))
    assert a.ok and b.ok
    assert a.journal_digest == b.journal_digest
    assert (a.commits, a.all_ok, a.safety_ok) == (
        b.commits,
        b.all_ok,
        b.safety_ok,
    )
    ja = (tmp_path / "a" / "journal.jsonl").read_bytes()
    jb = (tmp_path / "b" / "journal.jsonl").read_bytes()
    assert ja == jb and ja


def test_draw_schedule_is_pure():
    assert draw_schedule(7, nodes=4) == draw_schedule(7, nodes=4)
    assert draw_schedule(7, nodes=4) != draw_schedule(8, nodes=4)


# ---- crash-point injection --------------------------------------------


def test_crash_injection_torn_tail_recovery(tmp_path):
    """A mid-run crash leaves a torn WAL tail (complete header, missing
    body); the restart must recover through WAL replay + state-sync and
    the committee must still pass every invariant."""
    schedule = {
        "version": SCHEDULE_VERSION,
        "seed": 12345,
        "nodes": 4,
        "duration_s": 9.0,
        "profile": "honest",
        "events": [
            {
                "kind": "crash",
                "node": 2,
                "at": 2.0,
                "restart_at": 4.0,
                "torn_bytes": 33,
            }
        ],
    }
    verdict = run_schedule(schedule, workdir=str(tmp_path))
    assert verdict.ok, verdict.failures
    assert verdict.commits > 0
    # the torn tail really landed and recovery really ran: the journal
    # records both halves of the injected crash
    journal = (tmp_path / "journal.jsonl").read_text()
    assert "node 2 crashed (torn tail 33B)" in journal
    assert "node 2 restarted" in journal


def test_crash_with_last_block_append_torn_forgets_the_block(
    tmp_path, monkeypatch
):
    """The blocks a synchronizer keeps die with the node's incarnation.
    A node crashed INSIDE the append of its last block (the log cut in
    the middle of that block's record, so nothing after it happened
    either) and restarted holds no kept block: it does not answer for
    the torn block from memory, the recovered store does not hold it,
    and the node asks the committee for it again."""
    seen: dict = {}
    crash, restart = SimCluster.crash, SimCluster.restart

    async def crash_inside_last_append(self, i, torn_bytes=0):
        node = self.nodes[i]
        seen["sync"] = node.stack.synchronizer
        seen["torn"] = torn = list(seen["sync"]._kept.values())[-1]
        await crash(self, i, 0)
        wal = os.path.join(node.path, "wal.log")
        with open(wal, "rb") as f:
            data = f.read()
        at = off = 0
        while off + 8 <= len(data):  # the last record under the digest
            klen, vlen = struct.unpack_from("<II", data, off)
            if data[off + 8 : off + 8 + klen] == torn.digest().to_bytes():
                at = off
            off += 8 + klen + vlen
        assert at and off == len(data)
        with open(wal, "r+b") as f:
            f.truncate(at + 8 + 32 + len(torn.serialize()) // 2)

    async def restart_and_look(self, i):
        await restart(self, i)
        node = self.nodes[i]
        sync = node.stack.synchronizer
        key = seen["torn"].digest().to_bytes()
        seen["restarted"] = (
            sync is not seen["sync"]
            and seen["torn"].digest() not in sync._kept
            and await node.store.read(key) is None
        )

    monkeypatch.setattr(SimCluster, "crash", crash_inside_last_append)
    monkeypatch.setattr(SimCluster, "restart", restart_and_look)
    schedule = {
        "version": SCHEDULE_VERSION,
        "seed": 12345,
        "nodes": 4,
        "duration_s": 9.0,
        "profile": "honest",
        "events": [
            {"kind": "crash", "node": 2, "at": 2.0, "restart_at": 4.0}
        ],
    }
    verdict = run_schedule(schedule, workdir=str(tmp_path))
    assert verdict.ok, verdict.failures
    assert seen["restarted"] is True
    # the restarted incarnation's journal: the torn block is asked for
    # again, arrives, and the node commits on
    torn = seen["torn"]
    events = []
    journals = tmp_path / "journals"
    for name in sorted(os.listdir(journals)):
        if name.startswith(seen["sync"]._node[:4]):
            for line in (journals / name).read_text().splitlines():
                events.append(json.loads(line))
    asked = [
        e["s"] for e in events
        if e["e"] == "sync.req" and e["d"] == str(torn.digest())
    ]
    assert asked, "the torn block was never requested again"
    arrived = [
        e["s"] for e in events
        if e["e"] == "sync.done" and e["d"] == str(torn.digest())
        and e["s"] > asked[0]
    ]
    assert arrived, "the torn block never arrived again"
    assert any(e["e"] == "commit" and e["s"] > arrived[0] for e in events)


# ---- shrinker ----------------------------------------------------------


def test_shrinker_converges_on_planted_safety_bug():
    """Plant a collusion event inside an otherwise-honest schedule: the
    run must FAIL (profile 'honest' tolerates no divergence), and the
    shrinker must strip the innocent link noise down to exactly the
    planted event."""
    schedule = draw_schedule(48, nodes=4)  # honest, several link events
    assert schedule["profile"] == "honest"
    planted = {
        "kind": "byz",
        "policy": "collude",
        "nodes": [0, 1],
        "at": 1.0,
        "until": None,
    }
    schedule["events"] = schedule["events"] + [planted]
    verdict = run_schedule(schedule)
    assert not verdict.ok
    assert not verdict.safety_ok
    minimal = shrink(schedule)
    assert minimal["events"] == [planted]
    # the minimal schedule still reproduces, and removing the planted
    # event really is what makes it pass again
    assert not run_schedule(minimal).ok
    clean = dict(minimal, events=[])
    assert run_schedule(clean).ok


# ---- regression corpus ------------------------------------------------


def _corpus():
    with open(CORPUS) as f:
        corpus = json.load(f)
    assert corpus["version"] == SCHEDULE_VERSION, (
        "sim_seeds.json predates a schedule-format bump: re-derive the "
        "corpus expectations"
    )
    return corpus


@pytest.mark.parametrize(
    "entry", _corpus()["entries"], ids=lambda e: f"seed-{e['seed']}"
)
def test_regression_corpus(entry):
    """Every seed that ever produced an invariant failure during the sim
    plane's development, replayed against today's tree.  Entries with an
    inline ``schedule`` were promoted by the guided adversary search
    (docs/FAULTS.md): those must replay to the SAME verdict, the same
    threat set, and a byte-identical journal digest."""
    if "schedule" in entry:
        schedule = entry["schedule"]
        assert schedule["profile"] == entry["profile"]
        verdict = run_schedule(schedule)
        assert verdict.ok == entry["ok"], (entry["note"], verdict.failures)
        assert list(verdict.threats) == list(entry.get("threats", [])), (
            entry["note"],
            verdict.threats,
        )
        assert verdict.journal_digest == entry["journal_digest"], (
            entry["note"],
            "journal digest diverged from the promoted counterexample",
        )
        return
    schedule = draw_schedule(entry["seed"], nodes=_corpus()["nodes"])
    assert schedule["profile"] == entry["profile"]
    verdict = run_schedule(schedule)
    assert verdict.ok == entry["ok"], (entry["note"], verdict.failures)


# ---- ported e2e: crash + restart under partition ----------------------


def test_crash_restart_under_partition(tmp_path):
    """Virtual-time port of the subprocess e2e in
    tests/test_crash_rejoin_e2e.py (~150 s real time there): a crash
    INSIDE a split-brain window, and a rejoin inside a SECOND partition
    that isolates node 1 — the restarted node 3 must recover from its
    torn store via the reachable peers {0, 2} and its return restores
    the quorum.  Same fault geometry, same invariant stack, no ``slow``
    marker."""
    schedule = {
        "version": SCHEDULE_VERSION,
        "seed": 11,
        "nodes": 4,
        "duration_s": 12.0,
        "profile": "honest",
        "events": [
            # split-brain 0,1|2,3; node 3 crashes just as it bites,
            # leaving 2|1 — no quorum anywhere until the heal
            {
                "kind": "partition",
                "groups": [[0, 1], [2, 3]],
                "at": 1.5,
                "until": 3.5,
            },
            {
                "kind": "crash",
                "node": 3,
                "at": 1.6,
                "restart_at": 5.0,
                "torn_bytes": 24,
            },
            # second window: node 1 drops off while node 3 is still
            # down ({0,2} alone are below quorum); node 3 restarts
            # INSIDE this window and must resync from {0, 2}
            {
                "kind": "partition",
                "groups": [[0, 2, 3], [1]],
                "at": 4.5,
                "until": 7.5,
            },
        ],
    }
    verdict = run_schedule(schedule, workdir=str(tmp_path))
    assert verdict.ok, verdict.failures
    assert verdict.all_ok and verdict.safety_ok
    assert verdict.commits > 0
    journal = (tmp_path / "journal.jsonl").read_text()
    assert "node 3 crashed (torn tail 24B)" in journal
    assert "node 3 restarted" in journal
    # commits resumed after the last heal (t=7.5): liveness-after-heal
    # is part of check_run, but assert the rejoined node specifically
    # committed in its second lifetime
    node3 = (tmp_path / "logs" / "node-3.log").read_text()
    assert "Committed block" in node3
