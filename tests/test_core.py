"""Core state-machine tests, driven purely through its channels against a
real store, signature service, and synchronizer (reference
core_tests.rs:61-183), plus crash-recovery coverage the reference lacks
(SURVEY.md §4 gaps).
"""

import asyncio
from types import SimpleNamespace

import pytest

from hotstuff_tpu.consensus import Block, Core, ConsensusState, ProposerMessage, Synchronizer
from hotstuff_tpu.consensus.core import CONSENSUS_STATE_KEY, make_event_channels
from hotstuff_tpu.consensus.leader import LeaderElector
from hotstuff_tpu.consensus.wire import TAG_PROPOSE, TAG_VOTE, encode_timeout, encode_vote
from hotstuff_tpu.crypto import SignatureService
from hotstuff_tpu.crypto.service import CpuVerifier
from hotstuff_tpu.store import Store

from .common import (
    ancestor_lookups,
    async_test,
    chain,
    committee,
    fresh_base_port,
    keys,
    listener,
    signed_timeout,
    signed_vote,
)


def make_core(tmp_path, base, name_idx, timeout_ms=10_000):
    store = Store(str(tmp_path / "db"))
    com = committee(base)
    name, secret = keys()[name_idx]
    sig_service = SignatureService(secret)
    rx_events, rx_message, loopback = make_event_channels(2_000)
    tx_proposer: asyncio.Queue = asyncio.Queue()
    tx_commit: asyncio.Queue = asyncio.Queue()
    sync = Synchronizer(name, com, store, loopback, 10_000)
    core = Core(
        name,
        com,
        sig_service,
        CpuVerifier(),
        store,
        LeaderElector(com),
        sync,
        timeout_ms,
        rx_events=rx_events,
        rx_loopback=loopback,
        tx_proposer=tx_proposer,
        tx_commit=tx_commit,
    )
    return SimpleNamespace(
        core=core,
        store=store,
        committee=com,
        name=name,
        secret=secret,
        rx_message=rx_message,
        tx_proposer=tx_proposer,
        tx_commit=tx_commit,
        sync=sync,
    )


def teardown(h):
    h.core.shutdown()
    h.sync.shutdown()
    h.store.close()


@async_test
async def test_handle_proposal_votes_to_next_leader(tmp_path):
    """A valid round-1 proposal produces our vote at the round-2 leader
    (core_tests.rs:61-85)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0)  # not leader of rounds 1/2
    b1 = chain(1)[0]

    expected_vote = signed_vote(b1, h.name, h.secret)
    listen = asyncio.ensure_future(listener(base + 2, encode_vote(expected_vote)))
    await asyncio.sleep(0.05)

    h.core.spawn()
    await h.rx_message.put((TAG_PROPOSE, b1))
    await asyncio.wait_for(listen, timeout=2.0)
    teardown(h)


@async_test
async def test_generate_proposal_after_quorum(tmp_path):
    """2f+1 votes assemble a QC and, as the new leader, we ask the
    proposer for a block with that QC (core_tests.rs:87-132)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=2)  # leader of round 2
    b1 = chain(1)[0]
    h.core.spawn()

    for pk, sk in keys()[:3]:
        await h.rx_message.put((TAG_VOTE, signed_vote(b1, pk, sk)))

    # round advances also emit best-effort Cleanup pings; the MAKE is the
    # first non-cleanup message
    while True:
        message: ProposerMessage = await asyncio.wait_for(
            h.tx_proposer.get(), timeout=2.0
        )
        if message.kind == ProposerMessage.MAKE:
            break
    assert message.round == 2
    assert message.qc.hash == b1.digest()
    assert message.qc.round == 1
    assert message.tc is None
    teardown(h)


@async_test
async def test_commit_chain_head(tmp_path):
    """Processing a 3-block chain commits its head (core_tests.rs:134-160)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0)
    blocks = chain(3)
    h.core.spawn()
    for b in blocks:
        await h.rx_message.put((TAG_PROPOSE, b))

    committed = await asyncio.wait_for(h.tx_commit.get(), timeout=2.0)
    assert committed.digest() == blocks[0].digest()
    teardown(h)


@async_test
async def test_process_block_finds_its_ancestors_kept(tmp_path):
    """``store_block`` hands each block to the synchronizer once it is
    written, so the third block of a chain finds both ancestors kept
    (two hits, no store read of a block) and the 2-chain commit fires on
    the kept ``b0``: the object that was processed, not a decode of it."""
    h = make_core(tmp_path, fresh_base_port(), name_idx=0)
    blocks = chain(3)
    hits, misses = ancestor_lookups()
    await h.core._process_block(blocks[0])  # its parent is the genesis
    assert ancestor_lookups() == (hits, misses)
    await h.core._process_block(blocks[1])  # b1 kept, b0 the genesis
    assert ancestor_lookups() == (hits + 1, misses)
    assert h.tx_commit.empty()
    await h.core._process_block(blocks[2])  # both kept
    assert ancestor_lookups() == (hits + 3, misses)
    assert h.tx_commit.get_nowait() is blocks[0]
    assert list(h.sync._kept.values()) == blocks
    # what is kept is what the log holds, byte for byte
    for block in blocks:
        stored = await h.store.read(block.digest().to_bytes())
        assert stored == block.serialize()
        assert Block.deserialize(stored) == block
    teardown(h)


@async_test
async def test_store_block_keeps_a_block_only_once_written(tmp_path):
    """The kept blocks are a subset of the log: a block whose append
    fails is not kept, and one whose append returned is."""
    h = make_core(tmp_path, fresh_base_port(), name_idx=0)
    blocks = chain(2)
    put_many = h.store.engine.put_many

    def torn(pairs):
        raise OSError("disk full")

    h.store.engine.put_many = torn
    with pytest.raises(OSError):
        await h.core.store_block(blocks[0])
    assert h.sync._kept == {}
    assert await h.store.read(blocks[0].digest().to_bytes()) is None
    h.store.engine.put_many = put_many
    await h.core.store_block(blocks[0])
    assert list(h.sync._kept.values()) == [blocks[0]]
    assert await h.sync.get_parent_block(blocks[1]) is blocks[0]
    teardown(h)


@async_test
async def test_local_timeout_broadcasts(tmp_path):
    """The round timer firing broadcasts a signed Timeout to every peer
    (core_tests.rs:162-183)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0, timeout_ms=100)
    from hotstuff_tpu.consensus import QC

    expected = encode_timeout(signed_timeout(QC.genesis(), 1, h.name, h.secret))
    listens = [
        asyncio.ensure_future(listener(base + i, expected)) for i in (1, 2, 3)
    ]
    await asyncio.sleep(0.05)
    h.core.spawn()
    await asyncio.wait_for(asyncio.gather(*listens), timeout=2.0)
    teardown(h)


@async_test
async def test_timeout_join_round_sync(tmp_path):
    """f+1 distinct timeouts for a round AHEAD of ours make the core
    join that round and emit its own timeout (round synchronization): a
    node that missed a one-shot TC broadcast — routine during a
    snapshot-sync bootstrap — must not wedge one round behind a
    committee whose next TC needs this node's timeout."""
    from hotstuff_tpu.consensus import QC

    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0, timeout_ms=60_000)
    try:
        ks = keys()
        assert h.core.round == 1
        # one authority ahead of us: below the f+1 validity threshold,
        # we stay put
        await h.core._handle_timeout(
            signed_timeout(QC.genesis(), 3, ks[1][0], ks[1][1])
        )
        assert h.core.round == 1
        # a second distinct authority reaches f+1 = 2 of 4: join round
        # 3 and time it out ourselves — and with 3 of 4 timeouts the TC
        # assembles immediately, advancing the core into round 4
        await h.core._handle_timeout(
            signed_timeout(QC.genesis(), 3, ks[2][0], ks[2][1])
        )
        assert h.core.round == 4
    finally:
        teardown(h)


@async_test
async def test_local_timeout_fires_under_message_flood(tmp_path):
    """View-change liveness bound: a flood of cheap protocol messages
    queued ahead of the timer must delay the local timeout by at most
    one processing batch — the expiry check runs every loop iteration,
    not only when the timer pump's event drains through the merged
    queue (review finding on the r5 select-loop merge)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0, timeout_ms=150)
    from hotstuff_tpu.consensus import QC

    expected = encode_timeout(signed_timeout(QC.genesis(), 1, h.name, h.secret))
    listens = [
        asyncio.ensure_future(listener(base + i, expected)) for i in (1, 2, 3)
    ]
    await asyncio.sleep(0.05)
    # pre-load a deep backlog of far-future votes (free rejections, but
    # each occupies a queue slot ahead of any timer event)
    pk, sk = keys()[1]
    junk = signed_vote(chain(1)[0], pk, sk)
    junk.round = 10_000
    for _ in range(1_500):
        h.rx_message.put_nowait((TAG_VOTE, junk))
    h.core.spawn()
    # keep feeding while the timer runs so the queue never drains
    async def feeder():
        while True:
            try:
                h.rx_message.put_nowait((TAG_VOTE, junk))
            except asyncio.QueueFull:
                pass
            await asyncio.sleep(0.01)

    feed = asyncio.ensure_future(feeder())
    try:
        await asyncio.wait_for(asyncio.gather(*listens), timeout=2.0)
    finally:
        feed.cancel()
    teardown(h)


@async_test
async def test_loopback_backlog_drains_without_external_wakeups(tmp_path):
    """>64 loopback blocks queued in one burst exceed the per-iteration
    drain cap; the re-armed wake token must keep the loop processing
    them with NO network traffic or timer expiry (review finding: the
    capped drain could strand the tail until the round timeout)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0, timeout_ms=60_000)
    b1 = chain(1)[0]
    h.core.spawn()
    for _ in range(150):
        h.core.rx_loopback.put_nowait(b1)
    deadline = asyncio.get_running_loop().time() + 2.0
    while h.core.rx_loopback.qsize() > 0:
        assert asyncio.get_running_loop().time() < deadline, (
            f"loopback backlog stranded: {h.core.rx_loopback.qsize()} left"
        )
        await asyncio.sleep(0.02)
    teardown(h)


@async_test
async def test_loopback_processed_under_message_flood(tmp_path):
    """Loopback liveness bound: the node's own/sync-resumed blocks ride
    a priority channel drained every iteration, never queued behind the
    network backlog (review finding on the r5 select-loop merge) — a
    loopback proposal still produces our vote while junk floods the
    message queue."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0, timeout_ms=60_000)
    b1 = chain(1)[0]
    expected_vote = signed_vote(b1, h.name, h.secret)
    listen = asyncio.ensure_future(listener(base + 2, encode_vote(expected_vote)))
    await asyncio.sleep(0.05)

    pk, sk = keys()[1]
    junk = signed_vote(b1, pk, sk)
    junk.round = 10_000
    for _ in range(1_500):
        h.rx_message.put_nowait((TAG_VOTE, junk))
    h.core.spawn()

    async def feeder():
        while True:
            try:
                h.rx_message.put_nowait((TAG_VOTE, junk))
            except asyncio.QueueFull:
                pass
            await asyncio.sleep(0.01)

    feed = asyncio.ensure_future(feeder())
    try:
        await h.core.rx_loopback.put(b1)
        await asyncio.wait_for(listen, timeout=2.0)
    finally:
        feed.cancel()
    teardown(h)


@async_test
async def test_state_persisted_after_vote(tmp_path):
    """Any state-changing iteration rewrites ConsensusState (the fork's
    crash-recovery addition, core.rs:484-492)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0)
    b1 = chain(1)[0]
    h.core.spawn()
    await h.rx_message.put((TAG_PROPOSE, b1))
    await asyncio.sleep(0.3)

    raw = await h.store.read(CONSENSUS_STATE_KEY)
    assert raw is not None
    state = ConsensusState.deserialize(raw)
    assert state.last_voted_round == 1
    teardown(h)


@async_test
async def test_recovery_resumes_round(tmp_path):
    """A restarted core reloads its persisted round and (as leader of that
    round) immediately proposes — no test exists for this in the
    reference (SURVEY.md §4)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=3)  # leader of round 7 (7 % 4 == 3)
    state = ConsensusState(round_=7, last_voted_round=6, last_committed_round=5)
    await h.store.write(CONSENSUS_STATE_KEY, state.serialize())

    h.core.spawn()
    message: ProposerMessage = await asyncio.wait_for(
        h.tx_proposer.get(), timeout=2.0
    )
    assert message.kind == ProposerMessage.MAKE
    assert message.round == 7
    assert h.core.last_voted_round == 6
    assert h.core.last_committed_round == 5
    teardown(h)


@async_test
async def test_allow_empty_proposal_when_payloads_in_flight(tmp_path):
    """A Make issued while payload-carrying blocks are uncommitted sets
    allow_empty, so the next leader can advance the 2-chain with an empty
    block instead of parking the commit until the producer's next burst
    (this build's latency fix; the reference always defers,
    proposer.rs:74-78)."""
    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=3)  # leader of round 3
    blocks = chain(2)  # payload-carrying blocks for rounds 1..2
    h.core.spawn()
    for b in blocks:
        await h.rx_message.put((TAG_PROPOSE, b))

    # voting on b2 as round-3 leader needs 2f+1 votes to form the QC
    for pk, sk in keys()[:3]:
        await h.rx_message.put((TAG_VOTE, signed_vote(blocks[1], pk, sk)))

    while True:
        message: ProposerMessage = await asyncio.wait_for(
            h.tx_proposer.get(), timeout=2.0
        )
        if message.kind == ProposerMessage.MAKE:
            break
    assert message.round == 3
    # blocks 1..2 carry payloads and nothing is committed yet
    assert message.allow_empty
    teardown(h)


@async_test
async def test_proposer_makes_empty_block_when_allowed():
    """Proposer with an empty buffer: allow_empty Make emits an empty
    block on the loopback; without allow_empty it defers."""
    from hotstuff_tpu.consensus.proposer import Proposer

    name, secret = keys()[0]
    com = committee(fresh_base_port())
    loopback: asyncio.Queue = asyncio.Queue()
    proposer = Proposer(
        name,
        com,
        SignatureService(secret),
        rx_producer=asyncio.Queue(),
        rx_message=asyncio.Queue(),
        tx_loopback=loopback,
    )
    from hotstuff_tpu.consensus.messages import QC

    # deferred: no payloads, allow_empty=False
    await proposer._make_block(5, QC.genesis(), None, allow_empty=False)
    assert proposer.deferred is not None and loopback.empty()

    # allow_empty=True -> an empty block is created and looped back
    # (broadcast ACK-wait is cancelled on shutdown; peers are not up)
    task = asyncio.ensure_future(
        proposer._make_block(5, QC.genesis(), None, allow_empty=True)
    )
    block = await asyncio.wait_for(loopback.get(), timeout=2.0)
    assert block.round == 5 and block.payloads == ()
    task.cancel()
    proposer.shutdown()


@async_test
async def test_wrong_leader_proposal_rejected(tmp_path):
    """A Byzantine node proposing out of turn is rejected: no vote is
    emitted for a round-1 block authored by anyone but round 1's leader
    (core.rs:420-427 WrongLeader)."""
    from .common import qc_for_block, signed_block
    from hotstuff_tpu.crypto import Digest
    from hotstuff_tpu.consensus.messages import QC

    base = fresh_base_port()
    h = make_core(tmp_path, base, name_idx=0)
    # round 1's leader is keys()[1 % 4]; author with keys()[3] instead
    author, secret = keys()[3]
    bad = signed_block(author, secret, 1, qc=QC.genesis(), payload=Digest.random())

    listen = asyncio.ensure_future(listener(base + 2))  # round-2 leader's port
    await asyncio.sleep(0.05)
    h.core.spawn()
    await h.rx_message.put((TAG_PROPOSE, bad))
    # the proposal must NOT produce a vote
    with __import__("pytest").raises(asyncio.TimeoutError):
        await asyncio.wait_for(asyncio.shield(listen), timeout=0.6)
    listen.cancel()
    teardown(h)


@async_test
async def test_timeout_backoff_grows_and_resets_on_progress(tmp_path):
    """Exponential view-change backoff (beyond reference parity): each
    consecutive local timeout stretches the round timer geometrically
    (capped); observing a newer QC snaps it back to the base delay."""
    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=100)
    try:
        core = h.core
        base = 0.1
        assert core.timer.duration == base
        # mark the committee ACTIVE (uncommitted payload block in
        # flight): idle timeouts deliberately never grow the backoff
        # (see test_idle_timeouts_keep_base_timer)
        core.last_payload_round = 1
        from hotstuff_tpu.consensus.errors import ConsensusError

        async def fire_timer():
            # as in Core.run: re-firing for the same round raises benign
            # AuthorityReuse from the aggregator, which the loop logs
            try:
                await core._local_timeout_round()
            except ConsensusError:
                pass

        for expected_exp in (1, 2, 3):
            await fire_timer()
            assert core._timeout_exponent == expected_exp
            assert core.timer.duration == base * 2**expected_exp
        # cap: exponent keeps counting but the duration is clamped
        core._timeout_cap_ms = 500
        await fire_timer()
        assert core.timer.duration == 0.5
        # FIRST TC after progress: retry at base once (a single dead
        # leader structurally costs two view changes per lap — paying
        # base + backed-off for it would halve fault throughput)
        core._advance_round(core.round, via_tc=True)
        assert core._timeout_exponent == 0
        assert core.timer.duration == base
        # CONSECUTIVE TCs (no QC between): keep the backed-off timer —
        # under a uniformly slow but live network TCs keep forming, and
        # resetting on every one would pin the timer at base forever
        await fire_timer()
        assert core._timeout_exponent == 1
        core._advance_round(core.round, via_tc=True)
        assert core._timeout_exponent == 1
        assert core.timer.duration == base * 2
        # a QC-driven advance IS progress: backoff and TC streak reset
        blocks = chain(4)
        qc = blocks[-1].qc
        core.round = qc.round  # pretend we stalled at the QC's round
        core._process_qc(qc)
        assert core._timeout_exponent == 0
        assert core._consecutive_tcs == 0
        assert core.timer.duration == base
    finally:
        teardown(h)


@async_test
async def test_idle_timeouts_keep_base_timer(tmp_path):
    """An IDLE committee (no proposals seen, nothing uncommitted in
    flight — e.g. waiting for the first client payload) must not grow
    the view-change backoff: a WAN f=3 committee was measured wedging
    to ZERO commits because boot-time idle rounds compounded the timer
    to 16 s+ before the first transaction arrived."""
    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=100)
    try:
        core = h.core
        base = 0.1
        from hotstuff_tpu.consensus.errors import ConsensusError

        async def fire_timer():
            try:
                await core._local_timeout_round()
            except ConsensusError:
                pass

        for _ in range(4):  # idle spin: timer must stay at base
            await fire_timer()
            core._advance_round(core.round, via_tc=True)
        assert core._timeout_exponent == 0
        assert core.timer.duration == base

        # a verified proposal for the current round marks it active:
        # the NEXT timeout is a real liveness signal and backs off
        core._saw_proposal = True
        await fire_timer()
        assert core._timeout_exponent == 1
        assert core.timer.duration == base * 2
    finally:
        teardown(h)


@async_test
async def test_timeout_burst_aggregate_verification(tmp_path):
    """A view-change storm's timeout flood arriving in one burst is
    signature-verified as ONE coalesced claim batch (flood entries over
    the same digest form one shared claim); a garbage timeout in the
    burst makes its group fall back to per-item verification, where it
    is rejected while the honest timeouts still land in the TC maker."""
    from hotstuff_tpu.consensus.wire import TAG_TIMEOUT
    from hotstuff_tpu.crypto import Signature
    from hotstuff_tpu.crypto.async_service import AsyncVerifyService
    from hotstuff_tpu.crypto.service import CpuVerifier

    class CountingVerifier(CpuVerifier):
        ones = 0
        many = 0

        def verify_one(self, d, pk, sig):
            CountingVerifier.ones += 1
            return super().verify_one(d, pk, sig)

        def verify_many(self, digests, pks, sigs, aggregate_ok=False):
            CountingVerifier.many += 1
            return super().verify_many(digests, pks, sigs)

    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=60_000)
    try:
        from hotstuff_tpu.consensus import QC

        h.core.verifier = CountingVerifier()
        h.core.averifier = AsyncVerifyService.for_backend(h.core.verifier)
        ks = keys()
        # clean burst: 3 timeouts over the same digest (round 1, genesis
        # high_qc) -> one flattened claim batch, zero per-item checks
        burst = [
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, pk, sk))
            for pk, sk in ks[:3]
        ]
        pre = await h.core._preverify_burst(burst)
        assert pre == {0, 1, 2}
        # one aggregated crypto call, zero per-item checks: with the
        # native lib the whole wave is ONE flat batch equation
        # (verify_many never runs); without it, one verify_many call
        from hotstuff_tpu.crypto import native_ed25519

        assert CountingVerifier.many == (
            0 if native_ed25519.available() else 1
        )
        assert CountingVerifier.ones == 0

        # poisoned burst: one garbage signature -> the group's shared
        # claim fails, nothing is preverified (per-item fallback happens
        # in _handle_timeout, where the garbage one raises)
        bad = signed_timeout(QC.genesis(), 1, ks[2][0], ks[2][1])
        bad.signature = Signature(b"\x01" * 64)
        burst_bad = [
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, ks[0][0], ks[0][1])),
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, ks[1][0], ks[1][1])),
            (TAG_TIMEOUT, bad),
        ]
        pre = await h.core._preverify_burst(burst_bad)
        assert pre == set()

        # NON-MEMBER authors must never enter an aggregate (the BLS
        # rogue-key precondition: only PoP-checked committee keys may
        # be summed) — a stranger's timeout is excluded from grouping
        # even when the rest of the burst is honest
        from hotstuff_tpu.crypto import generate_keypair

        spk, ssk = generate_keypair(b"\x77" * 32, 0)  # not in committee
        stranger = signed_timeout(QC.genesis(), 1, spk, ssk)
        burst_mixed = [
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, ks[0][0], ks[0][1])),
            (TAG_TIMEOUT, stranger),
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, ks[1][0], ks[1][1])),
        ]
        pre = await h.core._preverify_burst(burst_mixed)
        assert pre == {0, 2}  # members aggregate; the stranger never joins
        # the per-item path still accepts the honest ones and rejects
        # the garbage one
        await h.core._handle_timeout(burst_bad[0][1])
        from hotstuff_tpu.consensus.errors import InvalidSignature

        try:
            await h.core._handle_timeout(bad)
            raise AssertionError("garbage timeout accepted")
        except InvalidSignature:
            pass
    finally:
        teardown(h)


@async_test
async def test_timeout_burst_mixed_rounds_group_separately(tmp_path):
    """Timeouts for different rounds (distinct digests) in one burst
    form one claim group per round — each verifies independently, and on
    an aggregate-preferring backend (BLS) each group costs exactly one
    shared-message check."""
    from hotstuff_tpu.consensus import QC
    from hotstuff_tpu.consensus.wire import TAG_TIMEOUT
    from hotstuff_tpu.crypto.async_service import AsyncVerifyService
    from hotstuff_tpu.crypto.service import CpuVerifier

    class AggregateCountingVerifier(CpuVerifier):
        """Counts shared-claim checks the way a BLS backend would see
        them (prefers_aggregate routes shared claims to
        verify_shared_msg instead of flattening)."""

        prefers_aggregate = True
        shared = 0

        def verify_shared_msg(self, d, votes):
            AggregateCountingVerifier.shared += 1
            return super().verify_shared_msg(d, votes)

    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=60_000)
    try:
        h.core.verifier = AggregateCountingVerifier()
        h.core.averifier = AsyncVerifyService.for_backend(h.core.verifier)
        ks = keys()
        burst = [
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, ks[0][0], ks[0][1])),
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 2, ks[1][0], ks[1][1])),
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 1, ks[2][0], ks[2][1])),
            (TAG_TIMEOUT, signed_timeout(QC.genesis(), 2, ks[3][0], ks[3][1])),
        ]
        pre = await h.core._preverify_burst(burst)
        assert pre == {0, 1, 2, 3}
        assert AggregateCountingVerifier.shared == 2  # one aggregate per round
    finally:
        teardown(h)


@async_test
async def test_preverify_skips_far_future_votes(tmp_path):
    """Advisor r4: votes beyond the aggregator's ROUND_LOOKAHEAD bound
    are rejected by add_vote with ZERO crypto — the preverify batch must
    not convert that free rejection into signature work."""
    from hotstuff_tpu.consensus.aggregator import ROUND_LOOKAHEAD
    from hotstuff_tpu.consensus.messages import Vote
    from hotstuff_tpu.crypto import Signature

    class Counting(CpuVerifier):
        calls = 0

        def verify_many(self, d, p, s, aggregate_ok=False):
            Counting.calls += len(d)
            return super().verify_many(d, p, s)

        def verify_one(self, d, pk, sig):
            Counting.calls += 1
            return super().verify_one(d, pk, sig)

        def verify_shared_msg(self, d, votes):
            Counting.calls += len(votes)
            return super().verify_shared_msg(d, votes)

    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=60_000)
    try:
        h.core.verifier = Counting()
        pk, sk = keys()[1]
        far = Vote(
            hash=__import__("hotstuff_tpu.crypto", fromlist=["Digest"])
            .Digest.random(),
            round=h.core.round + ROUND_LOOKAHEAD + 1,
            author=pk,
        )
        far.signature = Signature.new(far.digest(), sk)
        pre = await h.core._preverify_burst([(TAG_VOTE, far)])
        assert pre == set()
        assert Counting.calls == 0

        # same bound for timeouts
        from .common import qc_for_block, signed_timeout

        t = signed_timeout(
            h.core.high_qc, h.core.round + ROUND_LOOKAHEAD + 1, pk, sk
        )
        from hotstuff_tpu.consensus.wire import TAG_TIMEOUT

        pre = await h.core._preverify_burst([(TAG_TIMEOUT, t)])
        assert pre == set()
        assert Counting.calls == 0
    finally:
        teardown(h)
