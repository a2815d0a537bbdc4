"""Component tests: timer, synchronizer, helper (reference
timer_tests.rs, synchronizer_tests.rs:5-110, helper_tests.rs:7-37).
"""

import asyncio

import pytest

from hotstuff_tpu.consensus import Block, Synchronizer, Timer
from hotstuff_tpu.consensus.helper import Helper
from hotstuff_tpu.consensus.wire import (
    TAG_PROPOSE,
    decode_message,
    encode_sync_request,
)
from hotstuff_tpu.consensus.synchronizer import KEPT_BLOCKS
from hotstuff_tpu.store import Store

from .common import (
    ancestor_lookups,
    async_test,
    chain,
    committee,
    fresh_base_port,
    keys,
    listener,
)


@async_test
async def test_timer_fires_after_delay():
    timer = Timer(50)
    timer.reset()
    await asyncio.wait_for(timer.wait(), timeout=1.0)


@async_test
async def test_timer_reset_postpones():
    timer = Timer(100)
    timer.reset()
    waiter = asyncio.ensure_future(timer.wait())
    await asyncio.sleep(0.06)
    timer.reset()  # push the deadline out
    await asyncio.sleep(0.06)
    assert not waiter.done()  # old deadline passed but reset extended it
    await asyncio.wait_for(waiter, timeout=1.0)


#: the synchronizer's kept blocks when a lookup is made: none, or the
#: blocks of another chain (nothing a lookup below asks for)
KEPT = pytest.mark.parametrize(
    "others_kept", [False, True], ids=["nothing-kept", "others-kept"]
)


def keep_others(sync: Synchronizer, others_kept: bool) -> None:
    if others_kept:
        for block in chain(3):
            sync.keep(block)


def count_reads(store: Store) -> list[bytes]:
    """The keys ``store``'s engine is asked for from here on."""
    reads: list[bytes] = []
    get = store.engine.get

    def counted(key):
        reads.append(key)
        return get(key)

    store.engine.get = counted
    return reads


def assert_same_block(got: Block, want: Block) -> None:
    assert got.digest() == want.digest()
    assert got.qc == want.qc and got.payloads == want.payloads
    assert got.serialize() == want.serialize()
    assert got == want


@async_test
async def test_synchronizer_parent_hit(tmp_path):
    store = Store(str(tmp_path / "db"))
    base = fresh_base_port()
    blocks = chain(2)
    await store.write(blocks[0].digest().to_bytes(), blocks[0].serialize())
    sync = Synchronizer(
        keys()[0][0], committee(base), store, asyncio.Queue(), 10_000
    )
    parent = await sync.get_parent_block(blocks[1])
    assert parent is not None
    assert parent.digest() == blocks[0].digest()
    sync.shutdown()
    store.close()


@async_test
async def test_synchronizer_kept_block_answers_without_a_store_read(tmp_path):
    """A block the core handed over after its write is the answer to the
    next lookup of it: the same object, no store read, and equal to what
    a decode of the stored bytes gives."""
    store = Store(str(tmp_path / "db"))
    blocks = chain(3)
    sync = Synchronizer(
        keys()[0][0], committee(fresh_base_port()), store, asyncio.Queue(),
        10_000,
    )
    for block in blocks[:2]:
        await store.write(block.digest().to_bytes(), block.serialize())
        sync.keep(block)
    reads = count_reads(store)
    hits, misses = ancestor_lookups()
    ancestors = await sync.get_ancestors(blocks[2])
    assert ancestors is not None
    assert ancestors[0] is blocks[0] and ancestors[1] is blocks[1]
    assert reads == []
    assert ancestor_lookups() == (hits + 2, misses)
    for kept, block in zip(ancestors, blocks):
        stored = await store.read(block.digest().to_bytes())
        assert_same_block(kept, Block.deserialize(stored))
    sync.shutdown()
    store.close()


@async_test
async def test_synchronizer_keeps_the_newest_blocks_only(tmp_path):
    """Past the bound the oldest kept block falls out, and a lookup of
    it answers from the store with an equal block."""
    store = Store(str(tmp_path / "db"))
    blocks = chain(KEPT_BLOCKS + 2)
    sync = Synchronizer(
        keys()[0][0], committee(fresh_base_port()), store, asyncio.Queue(),
        10_000,
    )
    for block in blocks[:-1]:
        await store.write(block.digest().to_bytes(), block.serialize())
        sync.keep(block)
    sync.keep(blocks[1])  # kept again: the newest now, and kept once
    assert list(sync._kept.values()) == blocks[2:-1] + [blocks[1]]
    reads = count_reads(store)
    hits, misses = ancestor_lookups()
    # blocks[0] fell out: the store answers, with a block of its own
    parent = await sync.get_parent_block(blocks[1])
    assert parent is not blocks[0]
    assert_same_block(parent, blocks[0])
    assert reads == [blocks[0].digest().to_bytes()]
    assert ancestor_lookups() == (hits, misses + 1)
    # the newest is kept
    assert await sync.get_parent_block(blocks[-1]) is blocks[-2]
    assert len(reads) == 1 and ancestor_lookups() == (hits + 1, misses + 1)
    sync.shutdown()
    store.close()


@async_test
async def test_synchronizer_restart_reads_ancestors_from_the_store(tmp_path):
    """The kept blocks die with the node's incarnation: a second
    synchronizer on the same store path starts with none and finds both
    ancestors in what the store recovered."""
    path = str(tmp_path / "db")
    blocks = chain(3)
    name, com = keys()[0][0], committee(fresh_base_port())
    store = Store(path)
    first = Synchronizer(name, com, store, asyncio.Queue(), 10_000)
    for block in blocks[:2]:
        await store.write(block.digest().to_bytes(), block.serialize())
        first.keep(block)
    first.shutdown()
    store.close()

    store = Store(path)
    second = Synchronizer(name, com, store, asyncio.Queue(), 10_000)
    assert second._kept == {}
    reads = count_reads(store)
    hits, misses = ancestor_lookups()
    ancestors = await second.get_ancestors(blocks[2])
    assert ancestors is not None
    for got, block in zip(ancestors, blocks):
        assert got is not block
        assert_same_block(got, block)
    assert len(reads) == 2 and ancestor_lookups() == (hits, misses + 2)
    assert second._kept == {}  # a lookup keeps nothing: store_block does
    second.shutdown()
    store.close()


@KEPT
@async_test
async def test_synchronizer_genesis(tmp_path, others_kept):
    store = Store(str(tmp_path / "db"))
    base = fresh_base_port()
    sync = Synchronizer(
        keys()[0][0], committee(base), store, asyncio.Queue(), 10_000
    )
    keep_others(sync, others_kept)
    before = ancestor_lookups()
    parent = await sync.get_parent_block(chain(1)[0])
    assert parent == Block.genesis()
    assert ancestor_lookups() == before  # the genesis answer is neither
    sync.shutdown()
    store.close()


@KEPT
@async_test
async def test_synchronizer_miss_requests_then_loopback(tmp_path, others_kept):
    """Store miss: a SyncRequest goes to the block author; once the parent
    is written, the suspended child comes back on the loopback channel
    (synchronizer_tests.rs miss case).  Blocks kept of another chain
    change nothing."""
    store = Store(str(tmp_path / "db"))
    base = fresh_base_port()
    blocks = chain(2)
    name = keys()[0][0]
    loopback: asyncio.Queue = asyncio.Queue()
    sync = Synchronizer(name, committee(base), store, loopback, 10_000)
    keep_others(sync, others_kept)

    # the author of blocks[1] will receive the sync request
    author_port = base + [pk for pk, _ in keys()].index(blocks[1].author)
    expected = encode_sync_request(blocks[0].digest(), name)
    listen = asyncio.ensure_future(listener(author_port, expected))
    await asyncio.sleep(0.05)

    assert await sync.get_parent_block(blocks[1]) is None
    await asyncio.wait_for(listen, timeout=2.0)

    # writing the parent wakes the waiter and re-injects the child
    await store.write(blocks[0].digest().to_bytes(), blocks[0].serialize())
    child = await asyncio.wait_for(loopback.get(), timeout=2.0)
    assert child.digest() == blocks[1].digest()
    sync.shutdown()
    store.close()


@KEPT
@async_test
async def test_synchronizer_snapshot_barrier(tmp_path, others_kept):
    """A missing parent certified at or below the floor (the adopted
    snapshot's commit cursor) resolves to the genesis stand-in instead of
    a network fetch: a snapshot rejoin must not backfill pre-snapshot
    ancestry, which may be unreachable under an active partition."""
    store = Store(str(tmp_path / "db"))
    base = fresh_base_port()
    blocks = chain(2)
    name = keys()[0][0]
    sync = Synchronizer(
        name, committee(base), store, asyncio.Queue(), 10_000
    )
    keep_others(sync, others_kept)
    child = blocks[1]  # parent blocks[0] deliberately NOT in the store
    # at/below the floor: stand-in, and no request or waiter is parked
    parent = await sync.get_parent_block(child, floor=child.qc.round)
    assert parent == Block.genesis()
    assert not sync._requests and not sync._pending
    # above the floor: the ordinary fetch path engages and suspends
    assert (
        await sync.get_parent_block(child, floor=child.qc.round - 1) is None
    )
    assert sync._requests and sync._pending
    # get_ancestors applies the barrier to both hops: the outer hop
    # finds nothing below the floor to fetch either
    sync2 = Synchronizer(
        name, committee(base), store, asyncio.Queue(), 10_000
    )
    keep_others(sync2, others_kept)
    ancestors = await sync2.get_ancestors(child, floor=child.qc.round)
    assert ancestors == (Block.genesis(), Block.genesis())
    assert not sync2._requests
    # join_floor is the same barrier, whatever floor the caller passes
    sync2.join_floor = child.qc.round
    assert await sync2.get_parent_block(child) == Block.genesis()
    assert not sync2._requests
    sync.shutdown()
    sync2.shutdown()
    store.close()


def test_parameters_reject_incoherent_backoff():
    """ADVICE r3: a backoff < 1.0 would geometrically SHRINK the round
    timer under consecutive timeouts (view-change storm from a typo); a
    cap below the base delay is equally incoherent."""
    import pytest

    from hotstuff_tpu.consensus.config import InvalidParameters, Parameters

    with pytest.raises(InvalidParameters):
        Parameters(timeout_backoff=0.5)
    with pytest.raises(InvalidParameters):
        Parameters(timeout_delay=5_000, timeout_cap_ms=1_000)
    with pytest.raises(InvalidParameters):
        Parameters.from_json({"timeout_backoff": 0.9})
    # the reference-parity fixed timer (backoff exactly 1.0) stays legal
    Parameters(timeout_backoff=1.0)


def test_leader_cache_distinguishes_same_epoch_committees():
    """ADVICE r3: the elector's key cache must never alias two distinct
    committee objects — including schedule entries that share the
    default epoch number (legal in existing committee files)."""
    from hotstuff_tpu.consensus.config import CommitteeSchedule
    from hotstuff_tpu.consensus.leader import RoundRobinLeaderElector

    base = fresh_base_port()
    c1 = committee(base)
    # a second epoch with the SAME default epoch number but its members
    # rotated: the leader sequence must follow the active committee
    c2 = committee(base + 100)
    drop = c2.sorted_keys()[0]
    del c2.authorities[drop]
    schedule = CommitteeSchedule([(1, c1), (100, c2)])
    elector = RoundRobinLeaderElector(schedule)
    assert elector.get_leader(4) in c1.authorities
    assert elector.get_leader(4) == c1.sorted_keys()[4 % 4]
    assert elector.get_leader(103) == c2.sorted_keys()[103 % 3]
    assert elector.get_leader(103) != drop


def test_proposer_inflight_bound_requeues_oldest():
    """ADVICE r3: inflight must not grow without bound when commit
    signals stall — the oldest undecided proposal's payloads return to
    the buffer instead."""
    import logging
    from collections import OrderedDict

    import hotstuff_tpu.consensus.proposer as P
    from hotstuff_tpu.consensus.proposer import Proposer
    from hotstuff_tpu.crypto import Digest

    proposer = Proposer.__new__(Proposer)  # state-only exercise
    proposer.pending = OrderedDict()
    proposer.committed_seen = OrderedDict()
    proposer.inflight = {}
    proposer.log = logging.getLogger("test-proposer")

    digests = [Digest(bytes([i]) * 32) for i in range(8)]
    # this node admitted them all: only a digest's home re-buffers it
    proposer.home = dict.fromkeys(digests, 0.0)
    proposer.orphans = {}
    for r in range(1, 6):
        proposer.inflight[r] = (digests[r],)
    proposer.committed_seen[digests[1]] = None  # round 1's payload committed
    old_cap = P.MAX_INFLIGHT
    P.MAX_INFLIGHT = 3
    try:
        while len(proposer.inflight) > P.MAX_INFLIGHT:
            proposer._requeue_oldest_inflight()
    finally:
        P.MAX_INFLIGHT = old_cap
    assert set(proposer.inflight) == {3, 4, 5}
    # round 1's payload was already committed -> NOT re-buffered;
    # round 2's was orphan-requeued
    assert list(proposer.pending) == [digests[2]]


@async_test
async def test_helper_replies_to_sync_request(tmp_path):
    """Helper reads the requested block and sends it back as a Propose
    (helper_tests.rs:7-37)."""
    store = Store(str(tmp_path / "db"))
    base = fresh_base_port()
    com = committee(base)
    block = chain(1)[0]
    await store.write(block.digest().to_bytes(), block.serialize())

    requests: asyncio.Queue = asyncio.Queue()
    helper = Helper(com, store, requests)
    helper.spawn()

    requester = keys()[1][0]
    requester_port = base + 1
    listen = asyncio.ensure_future(listener(requester_port))
    await asyncio.sleep(0.05)

    await requests.put((block.digest(), requester))
    frame = await asyncio.wait_for(listen, timeout=2.0)
    tag, payload = decode_message(frame)
    assert tag == TAG_PROPOSE
    assert payload.digest() == block.digest()
    helper.shutdown()
    store.close()
