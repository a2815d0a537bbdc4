"""Wire-level flow accounting tests (telemetry/flows.py, ISSUE 19).

Three contracts:

- **tag parity** — the accountant's first-byte -> class map is pinned
  against the LIVE wire constants (consensus/wire.py), so a tag
  renumbering is a test failure instead of a silently-mislabelled flow;
- **exact byte accounting** — across a fuzz corpus of frames driven
  through the real asyncio senders and a real Receiver (and through the
  native reactor when it is built), accounted bytes equal the exact
  encoded frame length, ``FRAME_OVERHEAD + len(payload)`` each;
- **determinism** — a same-seed sim double-run produces byte-identical
  per-node flow tables (runs entirely in virtual time, no ``slow``
  marker).
"""

from __future__ import annotations

import asyncio
import json
import random

import pytest

from hotstuff_tpu.network import Receiver, ReliableSender, SimpleSender
from hotstuff_tpu.telemetry.flows import (
    FRAME_OVERHEAD,
    FlowAccounting,
    frame_class,
)
from hotstuff_tpu.telemetry.taxonomy import FLOW_CLASSES

from .common import async_test, fresh_base_port


def _fuzz_corpus(seed: int, n: int = 64) -> list[bytes]:
    """Frames with every known tag byte plus unknown tags and an empty
    frame — sizes spread across the framing small/large paths."""
    rng = random.Random(seed)
    corpus: list[bytes] = [b""]
    tags = list(range(12)) + [0x41, 0xA2, 0xA3, 0x7F, 0xFF]
    for i in range(n - 1):
        tag = tags[i % len(tags)]
        body = rng.randbytes(rng.choice([0, 1, 37, 512, 4096]))
        corpus.append(bytes([tag]) + body)
    return corpus


def _wire_cost(corpus) -> int:
    return sum(FRAME_OVERHEAD + len(p) for p in corpus)


# ---- tag taxonomy parity ----------------------------------------------


def test_frame_class_pins_live_wire_tags():
    """Every class assignment mirrors the wire constants it claims to
    mirror — drift in consensus/wire.py must break HERE, not in a
    dashboard."""
    from hotstuff_tpu.consensus import wire

    assert frame_class(bytes([wire.TAG_PROPOSE])) == "propose"
    assert frame_class(bytes([wire.TAG_VOTE])) == "vote"
    assert frame_class(bytes([wire.TAG_TIMEOUT])) == "timeout"
    assert frame_class(bytes([wire.TAG_TC])) == "tc"
    assert frame_class(bytes([wire.TAG_SYNC_REQUEST])) == "sync-req"
    assert frame_class(bytes([wire.TAG_PRODUCER])) == "producer-v1"
    assert frame_class(bytes([wire.TAG_PRODUCER_V2])) == "producer-v2"
    # the whole state-transfer family folds into one class
    for tag in (
        wire.TAG_STATE_REQUEST,
        wire.TAG_STATE_MANIFEST,
        wire.TAG_STATE_CHUNK,
        wire.TAG_STATE_READ,
        wire.STATE_VALUE_TAG,
    ):
        assert frame_class(bytes([tag])) == "state-sync"
    assert frame_class(bytes([wire.TAG_RECONFIG])) == "reconfig"
    assert frame_class(bytes([wire.TAG_RELAY])) == "relay"
    assert frame_class(wire.ACK) == "ack"
    assert frame_class(bytes([wire.INGEST_ACK_TAG])) == "ingest-ack"
    # unknown tags and the empty frame land in "other", never dropped
    assert frame_class(b"\x7f junk") == "other"
    assert frame_class(b"") == "other"


def test_every_class_is_registered_in_the_taxonomy():
    corpus = _fuzz_corpus(0xF040, 128)
    for payload in corpus:
        assert frame_class(payload) in FLOW_CLASSES


# ---- accountant unit behaviour ----------------------------------------


def test_amplification_is_wire_over_logical():
    acc = FlowAccounting("n0", enabled=True)
    frame = bytes([0]) + b"p" * 96  # propose
    acc.logical(frame)  # ONE broadcast call...
    for peer in ("a", "b", "c"):
        acc.tx(peer, frame)  # ...fanned out to 3 peers
    assert acc.amplification() == {"propose": 3.0}
    # a retransmit inflates wire amp AND the separate retx ledger
    acc.tx("a", frame, retx=True)
    assert acc.amplification()["propose"] == pytest.approx(4.0)
    assert acc.retx_bytes() == FRAME_OVERHEAD + len(frame)


def test_snapshot_topk_elides_with_explicit_counter(monkeypatch):
    monkeypatch.setenv("HOTSTUFF_NET_TOPK", "3")
    acc = FlowAccounting("n0", enabled=True)
    # 10 peers, strictly decreasing byte totals so top-K is stable
    for i in range(10):
        acc.tx(f"peer-{i}", bytes([1]) + b"v" * (100 - i))
    snap = acc.snapshot()
    assert len(snap["peers"]) == 3
    assert snap["peers_elided"] == 7
    assert list(snap["peers"]) == ["peer-0", "peer-1", "peer-2"]
    # eliding peers never elides bytes: totals stay exact
    assert snap["tx_bytes"] == acc.tx_bytes()
    # TOPK=0 disables the cap outright
    monkeypatch.setenv("HOTSTUFF_NET_TOPK", "0")
    full = FlowAccounting("n1", enabled=True)
    for i in range(10):
        full.tx(f"peer-{i}", b"\x01x")
    assert len(full.snapshot()["peers"]) == 10
    assert full.snapshot()["peers_elided"] == 0


def test_disabled_accounting_is_inert():
    acc = FlowAccounting("n0", enabled=False)
    acc.tx("a", b"\x00data")
    acc.rx("a", b"\x01data")
    acc.logical(b"\x00data")
    assert acc.snapshot() == {"enabled": False}
    assert acc.table() == {"flows": {}, "logical": {}}


# ---- exact byte accounting through the real transports ----------------


class _CollectHandler:
    def __init__(self, expect: int):
        self.frames: list[bytes] = []
        self.expect = expect
        self.done = asyncio.Event()

    async def dispatch(self, writer, message: bytes) -> None:
        self.frames.append(message)
        await writer.send(b"Ack")
        if len(self.frames) >= self.expect:
            self.done.set()


@async_test
async def test_simple_sender_accounts_exact_frame_bytes():
    corpus = _fuzz_corpus(0xF041)
    port = fresh_base_port()
    rx_acc = FlowAccounting("rx", enabled=True)
    tx_acc = FlowAccounting("tx", enabled=True)
    handler = _CollectHandler(len(corpus))
    recv = Receiver("127.0.0.1", port, handler, flows=rx_acc)
    await recv.spawn()
    sender = SimpleSender(flows=tx_acc)
    for payload in corpus:
        await sender.send(("127.0.0.1", port), payload)
    await asyncio.wait_for(handler.done.wait(), timeout=10.0)

    expected = _wire_cost(corpus)
    assert tx_acc.tx_bytes() == expected
    assert rx_acc.rx_bytes() == expected
    # the receiver's ACK replies are charged on ITS tx side, one frame
    # of b"Ack" per dispatch
    assert rx_acc.tx_bytes() == len(corpus) * (FRAME_OVERHEAD + 3)
    # per-class split loses nothing: class totals sum to the totals
    split = tx_acc.class_totals()
    assert sum(c["tx_bytes"] for c in split.values()) == expected
    assert sum(c["tx_frames"] for c in split.values()) == len(corpus)
    sender.close()
    await recv.shutdown()


@async_test
async def test_reliable_sender_accounts_exact_frame_bytes():
    corpus = _fuzz_corpus(0xF042, 32)
    port = fresh_base_port()
    rx_acc = FlowAccounting("rx", enabled=True)
    tx_acc = FlowAccounting("tx", enabled=True)
    handler = _CollectHandler(len(corpus))
    recv = Receiver("127.0.0.1", port, handler, flows=rx_acc)
    await recv.spawn()
    sender = ReliableSender(flows=tx_acc)
    handles = [
        await sender.send(("127.0.0.1", port), payload) for payload in corpus
    ]
    await asyncio.wait_for(asyncio.gather(*handles), timeout=10.0)

    expected = _wire_cost(corpus)
    assert tx_acc.tx_bytes() == expected
    assert rx_acc.rx_bytes() == expected
    # every ACK resolved first-try on a clean localhost link: the
    # retransmit ledger must read exactly zero
    assert tx_acc.retx_bytes() == 0
    assert all(r[3] == 0 for r in tx_acc.table()["flows"].values())
    sender.close()
    await recv.shutdown()


@async_test
async def test_native_reactor_loopback_matches_python_ledger():
    """Native sender -> native receiver: the Python-side flow ledger and
    the C++ reactor's own counters agree on every byte (both sides
    include the length prefix)."""
    native = pytest.importorskip("hotstuff_tpu.network.native")

    corpus = _fuzz_corpus(0xF043, 24)
    port = fresh_base_port()
    rx_acc = FlowAccounting("rx", enabled=True)
    tx_acc = FlowAccounting("tx", enabled=True)
    # the empty frame is charged on arrival but swallowed before
    # dispatch (b"" doubles as the isolate-window sentinel), so the
    # handler sees one frame fewer than the wire carried
    dispatched = sum(1 for p in corpus if p)
    handler = _CollectHandler(dispatched)
    recv = native.NativeReceiver("127.0.0.1", port, handler, flows=rx_acc)
    await recv.spawn()
    reactor = native.Reactor.shared()
    before = reactor.counters()

    sender = native.NativeSimpleSender(flows=tx_acc)
    for payload in corpus:
        await sender.send(("127.0.0.1", port), payload)
    await asyncio.wait_for(handler.done.wait(), timeout=10.0)

    expected = _wire_cost(corpus)
    assert tx_acc.tx_bytes() == expected
    assert rx_acc.rx_bytes() == expected

    # reactor ground truth: both directions of this loopback ran through
    # the one shared reactor, so its cumulative deltas cover our frames
    # plus the receiver's ACK replies — nothing else ran native here
    acks = rx_acc.tx_bytes()
    # the ACKs are charged when queued and written by the reactor thread:
    # give it a moment to count the last ones
    for _ in range(200):
        after = reactor.counters()
        if after["tx_bytes"] - before["tx_bytes"] >= expected + acks:
            break
        await asyncio.sleep(0.01)
    assert after["tx_bytes"] - before["tx_bytes"] == expected + acks
    assert (
        after["tx_frames"] - before["tx_frames"]
        == len(corpus) + dispatched
    )
    assert after["rx_bytes"] - before["rx_bytes"] >= expected
    sender.close()
    await recv.shutdown()


# ---- sim determinism: byte-identical flow tables ----------------------


def test_same_seed_sim_runs_produce_byte_identical_flow_tables(tmp_path):
    from hotstuff_tpu.sim import draw_schedule, run_schedule

    schedule = draw_schedule(3, nodes=4, profile="honest")
    a = run_schedule(schedule, workdir=str(tmp_path / "a"))
    b = run_schedule(schedule, workdir=str(tmp_path / "b"))
    assert a.ok and b.ok
    assert a.flows and set(a.flows) == set(b.flows)
    assert json.dumps(a.flows, sort_keys=True) == json.dumps(
        b.flows, sort_keys=True
    )
    # the tables carry real consensus traffic, classed and non-empty
    wire = sum(
        row[0]
        for tables in a.flows.values()
        for t in tables
        for row in t["flows"].values()
    )
    assert wire > 0
    classes = {
        key.rsplit("|", 2)[2]
        for tables in a.flows.values()
        for t in tables
        for key in t["flows"]
    }
    assert {"propose", "vote"} <= classes <= set(FLOW_CLASSES)
