"""Digest relay (ISSUE 27): a payload's home hands its digest to the
node that makes the next block.

Proposer units (who relays what to whom, prune at processing, orphans
back to their home's front, no payloads on an unseen parent), the wire
kind, the receiver's hand-off, and two committees of seven in this
process: every single-homed payload commits exactly once, most blocks
carry payloads their author is not the home of, and a dead node's view
changes orphan blocks of relayed digests that commit once afterwards.
"""

import asyncio
import itertools
import logging
import random
import re

import pytest

from hotstuff_tpu.consensus import QC, Block, Consensus, Parameters
from hotstuff_tpu.consensus.consensus import ConsensusReceiverHandler
from hotstuff_tpu.consensus.core import ProposerMessage
from hotstuff_tpu.consensus.errors import SerializationError
from hotstuff_tpu.consensus.leader import LeaderElector
from hotstuff_tpu.consensus.proposer import Proposer
from hotstuff_tpu.consensus.wire import (
    MAX_PRODUCER_BATCH,
    TAG_RELAY,
    decode_message,
    encode_relay,
)
from hotstuff_tpu.crypto import Digest, SignatureService
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils.codec import Encoder

from .common import async_test, committee, keys

N = 7

# A range of its own, below the kernel's ephemeral ports: the shared
# counter of tests/common.py starts at 26,000 in every worker, and two
# committees of seven hold their ports for seconds while other files run.
_ports = itertools.count(30_000, 20)


def fresh_base_port() -> int:
    return next(_ports)


class Outbox:
    """Stands in for the core's best-effort sender."""

    def __init__(self):
        self.sent: list[tuple[tuple, tuple]] = []

    async def send(self, address, data: bytes) -> None:
        tag, digests = decode_message(data)
        assert tag == TAG_RELAY
        self.sent.append((address, digests))


def bare_proposer(idx: int, n: int = N):
    """Node ``idx`` (it leads round r when r % n == idx) with the
    elector and an outbox, no network."""
    com = committee(fresh_base_port(), n)
    name, secret = keys(n)[idx]
    outbox = Outbox()
    proposer = Proposer(
        name,
        com,
        SignatureService(secret),
        rx_producer=asyncio.Queue(),
        rx_message=asyncio.Queue(),
        tx_loopback=asyncio.Queue(),
        leader_elector=LeaderElector(com),
        relay_network=outbox,
    )
    return proposer, outbox, com


def digests(count: int, salt: int = 0) -> list[Digest]:
    return [
        Digest(bytes([salt]) + i.to_bytes(31, "big")) for i in range(count)
    ]


def block_of(round_: int, payloads, n: int = N, qc: QC | None = None) -> Block:
    """An unsigned block by ``round_``'s leader (the proposer reads a
    processed block, it does not verify one)."""
    return Block(
        qc=qc if qc is not None else QC.genesis(),
        tc=None,
        author=keys(n)[round_ % n][0],
        round=round_,
        payloads=tuple(payloads),
    )


# ---- who relays what, to whom ------------------------------------------


@pytest.mark.parametrize(
    "round_, made, relays",
    [
        # node 3 of 7 leads rounds 3, 10, 17, ...
        (5, True, True),  # leads in five rounds: hand them on
        (7, True, True),  # leads round 10, three away
        (8, True, False),  # leads round 10 = 8 + 2: proposes them itself
        (9, True, False),  # leads the very next round
        (10, True, True),  # block 10 is made: what came since goes on
        (10, False, False),  # a TC seated round 10: its Make is still due
        (9, False, False),
        (6, False, True),
    ],
)
@async_test
async def test_relay_goes_to_the_next_leader_unless_this_node_leads_soon(
    round_, made, relays
):
    proposer, outbox, com = bare_proposer(3)
    mine = digests(3)
    for d in mine:
        proposer._buffer_item(d)
    await proposer._relay(round_, made=made)
    if not relays:
        assert outbox.sent == [] and proposer.relay_frames == 0
    else:
        target = keys(N)[(round_ + 1) % N][0]
        assert outbox.sent == [(com.address(target), tuple(mine))]
        assert (proposer.relay_frames, proposer.relayed_digests) == (1, 3)
    # once a round, whoever asks
    await proposer._relay(round_, made=True)
    assert len(outbox.sent) == int(relays)
    proposer.shutdown()


@async_test
async def test_only_home_digests_are_relayed_and_until_a_block_carries_them():
    proposer, outbox, _ = bare_proposer(3)
    mine, theirs = digests(4), digests(5, salt=1)
    proposer._buffer_item(mine[0])
    proposer._buffer_item(tuple(theirs))  # a peer's relay frame
    for d in mine[1:]:
        proposer._buffer_item(d)
    assert list(proposer.pending) == [mine[0], *theirs, *mine[1:]]
    await proposer._relay(4, made=True)
    assert outbox.sent[-1][1] == tuple(mine)  # relayed-in: never relayed on
    # nothing carried them yet (the frame came after the target's Make):
    # the next round's frame is the retry, to the next leader after it
    await proposer._relay(5, made=True)
    assert outbox.sent[-1][1] == tuple(mine)
    assert outbox.sent[-1][0] != outbox.sent[-2][0]
    # block 6 carries two of ours and two of theirs: they leave the buffer
    proposer._on_processed(block_of(6, mine[:2] + theirs[:2]))
    await proposer._relay(6, made=True)
    assert outbox.sent[-1][1] == tuple(mine[2:])
    assert list(proposer.pending) == [*theirs[2:], *mine[2:]]
    # the wait (admitted here -> in a processed block) is the home's alone
    assert proposer.wait_count == 2
    proposer.shutdown()


@async_test
async def test_a_relay_frame_is_capped():
    proposer, outbox, _ = bare_proposer(3)
    mine = digests(MAX_PRODUCER_BATCH + 40)
    for d in mine:
        proposer._buffer_item(d)
    await proposer._relay(4, made=True)
    assert outbox.sent[0][1] == tuple(mine[:MAX_PRODUCER_BATCH])
    proposer.shutdown()


@async_test
async def test_a_bare_proposer_relays_nothing():
    name, secret = keys()[0]
    proposer = Proposer(
        name, committee(fresh_base_port()), SignatureService(secret),
        rx_producer=asyncio.Queue(), rx_message=asyncio.Queue(),
        tx_loopback=asyncio.Queue(),
    )
    proposer._buffer_item(digests(1)[0])
    await proposer._relay(6, made=True)
    assert proposer.relay_frames == 0
    proposer.shutdown()


@async_test
async def test_relay_follows_the_cores_messages_in_their_order():
    """Through ``run()``: an admission sends at once, one frame a
    target for the whole burst; a round advance by a QC sends nothing
    (the round's block comes right behind it and is pruned first); the
    processed block does; an advance by a TC does."""
    proposer, outbox, com = bare_proposer(3)
    mine = digests(3)
    task = proposer.spawn()
    for d in mine:
        await proposer.rx_producer.put(d)
    await proposer.rx_message.put(ProposerMessage.cleanup([4]))
    await asyncio.sleep(0.05)
    # no block seen yet: blocks 1 and 2 are the next two to be made
    assert outbox.sent == [
        (com.address(keys(N)[r][0]), tuple(mine)) for r in (1, 2)
    ]
    assert (proposer.relay_frames, proposer.early_frames) == (2, 2)
    del outbox.sent[:]
    await proposer.rx_message.put(
        ProposerMessage.cleanup([3, 4, 5], block=block_of(5, mine[:1]))
    )
    await asyncio.sleep(0.05)
    assert [sent for _, sent in outbox.sent] == [tuple(mine[1:])]
    # an old block (a sync reply) relays nothing, whatever it carries
    await proposer.rx_message.put(
        ProposerMessage.cleanup([1, 2, 3], block=block_of(3, []))
    )
    await proposer.rx_message.put(
        ProposerMessage.cleanup([5], tc_entered=6)
    )
    await asyncio.sleep(0.05)
    assert [sent for _, sent in outbox.sent] == [tuple(mine[1:])] * 2
    assert proposer.relayed_round == 6
    assert proposer.early_frames == 2
    task.cancel()
    proposer.shutdown()


# ---- at admission: to the makers of the next two blocks (ISSUE 34) ------


async def admit(proposer, items):
    """One wake-up of the producer queue, as ``run()`` makes it: the
    burst is buffered, then what it admitted is relayed."""
    for item in items:
        proposer._buffer_item(item)
    await proposer._relay_admitted()


def leaders_sent(outbox, com):
    """The rounds' leaders (their index mod N) each frame went to."""
    index = {com.address(keys(N)[i][0]): i for i in range(N)}
    return [index[address] for address, _ in outbox.sent]


@pytest.mark.parametrize(
    "seen, by_tc, targets",
    [
        # node 3 of 7 leads rounds 3, 10, 17, ...; ``seen``: the newest
        # block it saw processed, or the round a TC has seated
        (5, False, [6, 7]),  # blocks 6 and 7 are the next two to be made
        (6, False, [7, 8]),
        (7, False, [8, 9]),  # it leads round 10, three away
        (8, False, []),  # 9 and 10: it leads the second
        (9, False, []),  # block 9 is made: it makes the very next block
        (10, False, [11, 12]),  # its own block is made: what comes now goes on
        # after a TC block ``seen`` itself is still to be made: the pair
        # is counted from it
        (6, True, [6, 7]),
        (8, True, [8, 9]),
        (9, True, []),  # 9 and 10: it leads the second
        (10, True, []),  # its own Make is behind the TC
        (11, True, [11, 12]),
    ],
)
@async_test
async def test_an_admitted_digest_goes_at_once_to_the_makers_of_the_next_two_blocks(
    seen, by_tc, targets
):
    proposer, outbox, com = bare_proposer(3)
    await proposer._relay(seen, made=not by_tc)  # nothing admitted yet
    assert outbox.sent == []
    mine = digests(3)
    await admit(proposer, mine)
    assert leaders_sent(outbox, com) == [t % N for t in targets]
    assert all(sent == tuple(mine) for _, sent in outbox.sent)
    assert proposer.early_frames == proposer.relay_frames == len(targets)
    assert proposer.relayed_digests == 3 * len(targets)
    # a wake-up that admits nothing sends nothing
    await admit(proposer, [])
    await admit(proposer, [mine[0]])  # a duplicate of a buffered payload
    assert proposer.relay_frames == len(targets)
    proposer.shutdown()


@async_test
async def test_only_orphans_go_early_when_this_node_leads_soon():
    proposer, outbox, com = bare_proposer(3)
    mine = digests(3)
    await admit(proposer, mine[:2])
    assert leaders_sent(outbox, com) == [1, 2]  # no block seen: rounds 1, 2
    del outbox.sent[:]
    proposer._on_processed(block_of(2, mine[:1]))  # block 2 carries the first
    await proposer._relay(8, made=True)
    # it leads round 10, the second of the pair 9, 10: the other digest
    # waits for its own block, and nothing is an orphan yet
    assert outbox.sent == []
    # the chain commits through round 5 without block 2
    proposer._resolve_inflight(
        ProposerMessage.cleanup([], payloads=set(), committed_round=5)
    )
    assert list(proposer.pending) == mine[:2]
    await admit(proposer, mine[2:])
    # the new digest rides in block 10; what block 2 carried and lost
    # goes to the other maker of the pair, and never to this node itself
    assert outbox.sent == [(com.address(keys(N)[9 % N][0]), (mine[0],))]
    assert proposer.early_frames == 3
    proposer.shutdown()


@async_test
async def test_a_relayed_in_digest_is_never_relayed_on_at_admission():
    proposer, outbox, _ = bare_proposer(3)
    mine, theirs = digests(2), digests(4, salt=1)
    await admit(proposer, [tuple(theirs)])  # a peer's relay frame alone
    assert outbox.sent == [] and proposer.early_frames == 0
    await admit(proposer, [tuple(theirs[:2]), mine[0], tuple(theirs[2:]), mine[1]])
    assert [sent for _, sent in outbox.sent] == [tuple(mine)] * 2
    proposer.shutdown()


@async_test
async def test_a_burst_is_one_frame_a_target_and_capped():
    proposer, outbox, _ = bare_proposer(3)
    await proposer._relay(4, made=True)
    mine = digests(MAX_PRODUCER_BATCH + 40)
    await admit(proposer, mine)
    assert [sent for _, sent in outbox.sent] == [
        tuple(mine[:MAX_PRODUCER_BATCH])
    ] * 2
    # the rest goes with the round's relay, and the first 512 only to
    # a leader that has not had them
    await proposer._relay(5, made=True)  # to leader(6): had the 512
    assert [sent for _, sent in outbox.sent[2:]] == [
        tuple(mine[MAX_PRODUCER_BATCH:])
    ]
    proposer.shutdown()


@async_test
async def test_the_rounds_relay_skips_what_its_target_has_and_goes_on_to_the_next():
    proposer, outbox, com = bare_proposer(3)
    await proposer._relay(4, made=True)
    early, late = digests(2), digests(2, salt=1)
    await admit(proposer, early)  # to leader(5) and leader(6)
    assert leaders_sent(outbox, com) == [5, 6]
    del outbox.sent[:]
    # block 5 was made before the copy landed and carries neither
    proposer._on_processed(block_of(5, []))
    await proposer._relay(5, made=True)
    assert outbox.sent == []  # leader(6) has them
    await admit(proposer, late)  # to leader(6) and leader(7 = 0 mod 7)
    assert leaders_sent(outbox, com) == [6, 0]
    del outbox.sent[:]
    # block 6 carries the early two alone (the late copy missed its make)
    proposer._on_processed(block_of(6, early))
    await proposer._relay(6, made=True)
    assert outbox.sent == []  # leader(7) has the late two, early are carried
    proposer._on_processed(block_of(7, []))
    await proposer._relay(7, made=True)
    # still pending after both copies: on to the next leader, once
    assert outbox.sent == [(com.address(keys(N)[8 % N][0]), tuple(late))]
    assert (proposer.early_frames, proposer.relay_frames) == (4, 5)
    # what committed is forgotten with its home entry
    task = proposer.spawn()
    await proposer.rx_message.put(
        ProposerMessage.cleanup([], payloads=set(early), committed_round=6)
    )
    await asyncio.sleep(0.05)
    assert set(proposer.relayed_to) == set(late) == set(proposer.home)
    assert not proposer.admitted_before.keys() & set(early)
    task.cancel()
    proposer.shutdown()


class Acked:
    """Stands in for the reliable sender: every peer has ACKed at once,
    so a bare proposer's ``run()`` comes back from a make."""

    async def broadcast(self, addresses, data: bytes):
        done = asyncio.get_running_loop().create_future()
        done.set_result(b"Ack")
        return [done] * len(addresses)

    def close(self) -> None:
        pass


class Wire:
    """A best-effort network of bare proposers: a relay frame lands in
    the producer queue of the proposer at its address, as the receiver
    hands it over, unless the test holds that address's frames back."""

    def __init__(self):
        self.queues: dict = {}
        self.held: dict = {}

    def attach(self, proposer, com):
        proposer.relay_network = self
        self.queues[com.address(proposer.name)] = proposer.rx_producer

    def hold(self, address):
        self.held[address] = []

    def release(self, address):
        for digests_ in self.held.pop(address):
            self.queues[address].put_nowait(digests_)

    async def send(self, address, data: bytes) -> None:
        tag, digests_ = decode_message(data)
        assert tag == TAG_RELAY
        if address in self.held:
            self.held[address].append(digests_)
        elif address in self.queues:
            self.queues[address].put_nowait(digests_)


def wired(*indices):
    """Bare proposers of one committee of seven on one ``Wire``."""
    com = committee(fresh_base_port(), N)
    wire = Wire()
    out = []
    for idx in indices:
        name, secret = keys(N)[idx]
        proposer = Proposer(
            name, com, SignatureService(secret),
            rx_producer=asyncio.Queue(), rx_message=asyncio.Queue(),
            tx_loopback=asyncio.Queue(),
            network=Acked(),
            leader_elector=LeaderElector(com),
        )
        wire.attach(proposer, com)
        out.append(proposer)
    return wire, com, out


async def make_on(proposer, parent: Block | None, round_: int) -> Block:
    """The core's messages for a leader: ``parent`` processed, then the
    Make on its QC; returns the block the proposer hands back."""
    qc = QC.genesis()
    if parent is not None:
        qc = QC(hash=parent.digest(), round=parent.round)
        await proposer.rx_message.put(
            ProposerMessage.cleanup([parent.round], block=parent)
        )
    await proposer.rx_message.put(ProposerMessage.make(round_, qc, None))
    return await asyncio.wait_for(proposer.tx_loopback.get(), 2.0)


@async_test
async def test_a_digest_admitted_mid_round_rides_in_the_very_next_block():
    """Node 3 saw block 4 processed; a client's digest comes in while
    block 5 is still to be made: it is in leader(5)'s buffer before that
    leader's Make, and block 5 carries it, where the round's relay
    would have sent it to leader(6) after block 5."""
    wire, com, (home, five, six) = wired(3, 5, 6)
    tasks = [p.spawn() for p in (home, five, six)]
    four = block_of(4, [])
    await home.rx_message.put(ProposerMessage.cleanup([4], block=four))
    await asyncio.sleep(0.02)
    mine = digests(2)
    for d in mine:
        await home.rx_producer.put(d)
    await asyncio.sleep(0.05)
    assert list(five.pending) == mine and list(six.pending) == mine
    assert five.home == {} and home.early_frames == 2
    made = await make_on(five, four, 5)
    assert (made.round, made.payloads) == (5, tuple(mine))
    assert five.proposed_relayed == 2
    # every node processes block 5: the copy at leader(6) is pruned, and
    # its block on that chain carries nothing twice
    await home.rx_message.put(ProposerMessage.cleanup([5], block=made))
    await six.rx_message.put(ProposerMessage.cleanup([5], block=made))
    await six.rx_message.put(
        ProposerMessage.make(
            6, QC(hash=made.digest(), round=5), None, allow_empty=True
        )
    )
    empty = await asyncio.wait_for(six.tx_loopback.get(), 2.0)
    assert (empty.round, empty.payloads) == (6, ())
    await asyncio.sleep(0.02)
    # the first carrying block is the one made right after the admission
    assert (home.wait_count, home.carried_next) == (2, 2)
    assert home.relay_frames == 2  # the round's relay had nothing left
    for t in tasks:
        t.cancel()
    for p in (home, five, six):
        p.shutdown()


@async_test
async def test_a_copy_that_lands_after_the_make_is_pruned_and_never_proposed_twice():
    """Two leaders hold one digest and both make blocks on one chain:
    the copy for leader(5) lands after its Make, so block 6 carries the
    digest; leader(5) prunes its late copy when it processes block 6
    and does not propose it when it leads again."""
    wire, com, (home, five, six) = wired(3, 5, 6)
    tasks = [p.spawn() for p in (home, five, six)]
    four = block_of(4, [])
    await home.rx_message.put(ProposerMessage.cleanup([4], block=four))
    await asyncio.sleep(0.02)
    wire.hold(com.address(five.name))
    mine = digests(1)
    await home.rx_producer.put(mine[0])
    await asyncio.sleep(0.05)
    # leader(5) makes its block without it (another payload fires it)
    other = digests(1, salt=3)
    await five.rx_producer.put(tuple(other))
    made5 = await make_on(five, four, 5)
    assert made5.payloads == tuple(other)
    wire.release(com.address(five.name))  # the late copy lands
    await asyncio.sleep(0.05)
    assert list(five.pending) == mine
    made6 = await make_on(six, made5, 6)
    assert made6.payloads == tuple(mine)
    for p in (home, five):
        await p.rx_message.put(ProposerMessage.cleanup([5], block=made5))
        await p.rx_message.put(ProposerMessage.cleanup([6], block=made6))
    await asyncio.sleep(0.05)
    assert not five.pending and not home.pending and not six.pending
    # leader(5) leads round 12 on that chain: nothing of it comes back
    await five.rx_message.put(
        ProposerMessage.make(
            12, QC(hash=made6.digest(), round=6), None, allow_empty=True
        )
    )
    made12 = await asyncio.wait_for(five.tx_loopback.get(), 2.0)
    assert made12.payloads == ()
    # a copy of the frame that arrives now is refused: block 6 is tracked
    await five.rx_producer.put(tuple(mine))
    await asyncio.sleep(0.05)
    assert not five.pending
    # carried by block 6, the second block after its admission: not "next"
    assert (home.wait_count, home.carried_next) == (1, 0)
    for t in tasks:
        t.cancel()
    for p in (home, five, six):
        p.shutdown()


@async_test
async def test_carried_next_and_early_frames_count_what_they_say(caplog):
    proposer, outbox, _ = bare_proposer(3)
    await proposer._relay(4, made=True)
    first, second, own = digests(2), digests(2, salt=1), digests(1, salt=2)
    await admit(proposer, first)  # block 5 is the next to be made
    proposer._on_processed(block_of(5, first[:1]))  # carries one: next
    await proposer._relay(5, made=True)
    await admit(proposer, second)  # block 6 is the next
    proposer._on_processed(block_of(6, first[1:] + second[:1]))
    # first[1] was admitted before block 5: 6 is not its next block
    assert (proposer.wait_count, proposer.carried_next) == (3, 2)
    proposer._on_processed(block_of(8, second[1:]))  # two blocks late
    assert (proposer.wait_count, proposer.carried_next) == (4, 2)
    # what it proposes itself in the block it makes next counts too
    await proposer._relay(9, made=True)
    await admit(proposer, own)  # it leads round 10: nothing goes out
    task = asyncio.ensure_future(proposer._make_block(10, QC.genesis(), None))
    made = await asyncio.wait_for(proposer.tx_loopback.get(), 2.0)
    assert made.payloads == tuple(own)
    assert (proposer.wait_count, proposer.carried_next) == (5, 3)
    # two admissions with two targets each; the rounds' relays sent the rest
    assert proposer.early_frames == 4
    assert proposer.relay_frames == len(outbox.sent)
    assert not proposer.admitted_before
    with caplog.at_level(logging.INFO, logger="hotstuff_tpu.consensus.proposer"):
        proposer._log_stats()
    line = caplog.messages[-1]
    assert line.endswith("wait_n=5 early_frames=4 carried_next=3")
    assert line.startswith("Proposer stats: relayed=")
    task.cancel()
    proposer.shutdown()


# ---- exactly once: prune at processing, orphans, the unseen parent ------


@async_test
async def test_processed_blocks_prune_and_orphans_return_to_their_homes_front():
    proposer, _, _ = bare_proposer(3)
    mine, theirs = digests(5), digests(3, salt=1)
    for d in mine:
        proposer._buffer_item(d)
    proposer._buffer_item(tuple(theirs))
    # another author's block 5 carries two of ours and one relayed-in
    proposer._on_processed(block_of(5, [mine[1], mine[2], theirs[0]]))
    assert list(proposer.pending) == [mine[0], mine[3], mine[4], *theirs[1:]]
    assert proposer.inflight == {5: (mine[1], mine[2], theirs[0])}
    # ... and block 6 carries one more of ours
    proposer._on_processed(block_of(6, [mine[3]]))
    # a copy of a relay frame that arrives now must not re-enter
    proposer._buffer_item((theirs[0],))
    assert theirs[0] not in proposer.pending
    # the chain commits through round 6 with block 6 and without block 5
    proposer._resolve_inflight(
        ProposerMessage.cleanup([], payloads={mine[3]}, committed_round=6)
    )
    # ours come back, oldest first, in FRONT; theirs[0] is its home's
    assert list(proposer.pending) == [
        mine[1], mine[2], mine[0], mine[4], *theirs[1:]
    ]
    assert proposer.inflight == {} and proposer._tracked() == set()
    assert list(proposer.orphans) == [mine[1], mine[2]]
    proposer.shutdown()


@async_test
async def test_orphans_are_relayed_even_when_this_node_leads_soon():
    """The node before a dead one sees every block of its own orphaned:
    what such a block carried goes to other leaders too, or it would be
    proposed and lost once a rotation for good."""
    proposer, outbox, _ = bare_proposer(3)
    mine = digests(3)
    for d in mine:
        proposer._buffer_item(d)
    proposer._on_processed(block_of(3, mine[:1]))
    proposer._resolve_inflight(
        ProposerMessage.cleanup([], payloads=set(), committed_round=5)
    )
    await proposer._relay(8, made=True)  # it leads round 10
    assert [sent for _, sent in outbox.sent] == [(mine[0],)]
    # a block carries it again: it is an orphan no more
    proposer._on_processed(block_of(9, mine[:1]))
    await proposer._relay(9, made=True)
    assert len(outbox.sent) == 1 and not proposer.orphans
    proposer.shutdown()


@async_test
async def test_an_orphan_that_a_later_block_carries_stays_out():
    proposer, _, _ = bare_proposer(3)
    mine = digests(2)
    for d in mine:
        proposer._buffer_item(d)
    proposer._on_processed(block_of(5, mine))  # to be orphaned
    proposer._on_processed(block_of(8, [mine[0]]))  # proposed again since
    proposer._resolve_inflight(
        ProposerMessage.cleanup([], payloads=set(), committed_round=6)
    )
    assert list(proposer.pending) == [mine[1]]
    assert proposer.inflight == {8: (mine[0],)}
    # a block seen twice is tracked once
    proposer._on_processed(block_of(8, [mine[0]]))
    assert proposer.inflight == {8: (mine[0],)}
    proposer.shutdown()


@pytest.mark.parametrize("allow_empty", [False, True])
@async_test
async def test_a_make_on_an_unseen_parent_takes_no_payloads(allow_empty):
    """Votes can overtake the proposal: the Make for round 10 names
    block 9 before the core has processed it, and block 9 may carry
    what is in this buffer."""
    proposer, _, _ = bare_proposer(3)
    mine = digests(4)
    for d in mine:
        proposer._buffer_item(d)
    parent = block_of(9, mine[:2])
    qc = QC(hash=parent.digest(), round=9)
    task = proposer.spawn()
    await proposer.rx_message.put(
        ProposerMessage.make(10, qc, None, allow_empty=allow_empty)
    )
    if allow_empty:
        made = await asyncio.wait_for(proposer.tx_loopback.get(), 2.0)
        assert (made.round, made.payloads) == (10, ())
        assert list(proposer.pending) == mine
    else:
        await asyncio.sleep(0.05)
        assert proposer.tx_loopback.empty() and proposer.deferred is not None
        # a payload's arrival does not fire it: the parent is still unseen
        await proposer.rx_producer.put(digests(1, salt=2)[0])
        await asyncio.sleep(0.05)
        assert proposer.tx_loopback.empty()
        # the parent's message does, and its payloads are gone by then
        await proposer.rx_message.put(
            ProposerMessage.cleanup([7, 8, 9], block=parent)
        )
        made = await asyncio.wait_for(proposer.tx_loopback.get(), 2.0)
        assert made.round == 10
        assert made.payloads == (*mine[2:], digests(1, salt=2)[0])
        assert proposer.proposed_home == 3
    task.cancel()
    proposer.shutdown()


@async_test
async def test_a_make_counts_whose_payloads_it_proposes():
    proposer, _, _ = bare_proposer(3)
    mine, theirs = digests(2), digests(3, salt=1)
    proposer._buffer_item(tuple(theirs))
    for d in mine:
        proposer._buffer_item(d)
    task = asyncio.ensure_future(
        proposer._make_block(10, QC.genesis(), None)
    )
    made = await asyncio.wait_for(proposer.tx_loopback.get(), 2.0)
    assert made.payloads == (*theirs, *mine)
    assert (proposer.proposed_relayed, proposer.proposed_home) == (3, 2)
    # its own block is tracked from its making: the core's message for
    # it changes nothing
    assert proposer.inflight == {10: made.payloads}
    proposer._on_processed(made)
    assert proposer.inflight == {10: made.payloads}
    task.cancel()
    proposer.shutdown()


# ---- the wire kind and the receiver's hand-off --------------------------


def test_relay_frame_round_trip():
    sent = digests(MAX_PRODUCER_BATCH)
    for count in (1, 17, MAX_PRODUCER_BATCH):
        tag, got = decode_message(encode_relay(sent[:count]), scheme="ed25519")
        assert tag == TAG_RELAY == 12 and got == tuple(sent[:count])
    for bad in ([], sent + sent[:1]):
        with pytest.raises(ValueError):
            encode_relay(bad)


def _relay_bytes(count: int, carried: int, tail: bytes = b"") -> bytes:
    enc = Encoder().u8(TAG_RELAY).u32(count)
    for d in digests(carried):
        enc.raw(d.to_bytes())
    return enc.finish() + tail


@pytest.mark.parametrize(
    "frame",
    [
        _relay_bytes(0, 0),  # an empty frame says nothing
        _relay_bytes(MAX_PRODUCER_BATCH + 1, MAX_PRODUCER_BATCH + 1),
        _relay_bytes(0xFFFFFFFF, 1),  # a count bomb
        _relay_bytes(3, 2),  # fewer digests than counted
        _relay_bytes(2, 2)[:-5],  # cut inside a digest
        _relay_bytes(2, 2, tail=b"\x00"),  # trailing bytes
        bytes([TAG_RELAY]),
    ],
    ids=["none", "over-cap", "bomb", "short", "cut", "trailing", "bare-tag"],
)
def test_malformed_relay_frames_are_refused(frame):
    with pytest.raises(SerializationError):
        decode_message(frame)


def test_relay_frame_truncations_and_bit_flips_never_crash():
    rng = random.Random(0xF027)
    frame = encode_relay(digests(9))
    for data in (
        [frame[:cut] for cut in range(len(frame))]
        + [frame + frame[:cut] for cut in range(1, 40)]
    ):
        with pytest.raises(SerializationError):
            decode_message(data)
    for _ in range(500):
        buf = bytearray(frame)
        for _ in range(rng.randrange(1, 9)):
            buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        try:
            decode_message(bytes(buf))
        except SerializationError:
            pass  # the only acceptable failure


class _Writer:
    def __init__(self):
        self.replies = []

    async def send(self, data: bytes) -> None:
        self.replies.append(data)


@async_test
async def test_the_receiver_hands_a_relay_frame_to_the_proposer_whole():
    class Admission:
        def admit(self, n):
            raise AssertionError("a relayed digest was admitted at its home")

    tx_consensus, tx_producer = asyncio.Queue(), asyncio.Queue(maxsize=1)
    handler = ConsensusReceiverHandler(
        tx_consensus, asyncio.Queue(), tx_producer, admission=Admission()
    )
    writer = _Writer()
    sent = digests(5)
    await handler.dispatch(writer, encode_relay(sent))
    assert tx_producer.get_nowait() == tuple(sent)
    # best effort: no ACK, nothing for the core, and a full queue drops
    # the frame instead of holding the receiver
    await handler.dispatch(writer, encode_relay(sent))
    await asyncio.wait_for(handler.dispatch(writer, encode_relay(sent)), 1.0)
    assert writer.replies == [] and tx_consensus.empty()
    assert tx_producer.qsize() == 1
    # a malformed one is dropped like any other frame
    await handler.dispatch(writer, _relay_bytes(3, 2))
    assert tx_producer.qsize() == 1


# ---- committees of seven in this process --------------------------------

RE_CREATED = re.compile(r"Created block (\d+) \(payloads (\S*)\) -> (\S+)")


class CreatedBlocks(logging.Handler):
    """Every ``Created block`` line: (author's name, round, payload ids,
    block id), as ``chipbench/logs.py`` reads them."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.blocks: list[tuple[str, int, list[str], str]] = []
        self.rebuffered = 0

    def emit(self, record):
        message = record.getMessage()
        if message.startswith("Re-buffering"):
            self.rebuffered += 1
            return
        m = RE_CREATED.match(message)
        if m is not None:
            ids = m.group(2).split(",") if m.group(2) else []
            self.blocks.append(
                (record.name.rsplit(".", 1)[-1], int(m.group(1)), ids,
                 m.group(3))
            )


async def _committee_of_seven(tmp_path, live, timeout_delay):
    com = committee(fresh_base_port(), N)
    nodes = []
    for i in live:
        name, secret = keys(N)[i]
        store = Store(str(tmp_path / f"db_{i}"))
        commits: asyncio.Queue = asyncio.Queue()
        stack = await Consensus.spawn(
            name, com,
            Parameters(timeout_delay=timeout_delay, sync_retry_delay=5_000),
            SignatureService(secret), store, commits,
            bind_host="127.0.0.1",
        )
        nodes.append((stack, commits, store))
    return nodes


async def _single_homed_run(tmp_path, live, count, gap_s, timeout_delay,
                            limit_s, seed, enough=None):
    """Feed up to ``count`` payloads (until ``enough(created, home)``,
    if given), each to ONE live node drawn from ``seed``, and follow
    node ``live[0]``'s commits until every payload fed is committed (or
    ``limit_s``).  Returns (home of each payload id, how often each was
    committed, the committed blocks, the ``Created block`` lines)."""
    created = CreatedBlocks()
    logger = logging.getLogger("hotstuff_tpu.consensus.proposer")
    old_level = logger.level
    logger.setLevel(logging.INFO)
    logger.addHandler(created)
    nodes = await _committee_of_seven(tmp_path, live, timeout_delay)
    rng = random.Random(seed)
    home: dict[str, str] = {}

    async def feed():
        for k in range(count):
            if enough is not None and enough(created, home):
                return
            digest = Digest.of(f"relay|{seed}|{k}".encode())
            stack = nodes[rng.randrange(len(nodes))][0]
            home[str(digest)] = str(stack.proposer.name)[:8]
            await stack.tx_producer.put(digest)
            await asyncio.sleep(gap_s)

    feeder = asyncio.ensure_future(feed())
    times: dict[str, int] = {}
    committed = []
    try:
        deadline = asyncio.get_running_loop().time() + limit_s
        commits = nodes[0][1]
        while not (feeder.done() and len(times) >= len(home)):
            left = deadline - asyncio.get_running_loop().time()
            if left <= 0:
                break
            try:
                block = await asyncio.wait_for(commits.get(), min(left, 1.0))
            except asyncio.TimeoutError:
                continue
            committed.append(block)
            for d in block.payloads:
                times[str(d)] = times.get(str(d), 0) + 1
    finally:
        feeder.cancel()
        for stack, _, _ in nodes:
            await stack.shutdown()
        for _, _, store in nodes:
            store.close()
        logger.removeHandler(created)
        logger.setLevel(old_level)
    return home, times, committed, created


@async_test
async def test_seven_nodes_commit_single_homed_payloads_once_by_relay(tmp_path):
    home, times, committed, created = await _single_homed_run(
        tmp_path, range(N), count=600, gap_s=0.002, timeout_delay=5_000,
        limit_s=60.0, seed=27,
    )
    assert set(times) == set(home), "a payload was lost"
    assert set(times.values()) == {1}, "a payload was committed twice"
    # no block of the run carries a payload twice either, committed or not
    proposed = [pid for _, _, ids, _ in created.blocks for pid in ids]
    assert len(proposed) == len(set(proposed))
    carrying = [b for b in created.blocks if b[2]]
    relayed = [
        b for b in carrying if any(home[pid] != b[0] for pid in b[2])
    ]
    # with six of seven payloads homed elsewhere, most blocks that carry
    # anything carry something their author is not the home of
    assert len(relayed) > len(carrying) / 2, (len(relayed), len(carrying))


@async_test
async def test_relayed_digests_of_orphaned_blocks_commit_once_afterwards(
    tmp_path,
):
    """Node 4 of 7 never starts.  The votes for block 3 (mod 7) go to
    it, so that block gathers no QC, a TC passes round 4 too, and block
    5 builds on block 2's QC: block 3 is orphaned every lap, with the
    digests other nodes relayed to its author.  Their homes re-buffer
    them and relay them again; each commits exactly once."""
    live = [i for i in range(N) if i != 4]
    doomed = str(keys(N)[3][0])[:8]

    def enough(created, home):
        # three blocks of the doomed leader that carry another node's
        # payload (it gets only what arrives in the round before its
        # own, a few milliseconds of a rotation that two timeouts fill)
        return 3 <= sum(
            author == doomed and any(home[pid] != author for pid in ids)
            for author, _, ids, _ in created.blocks
        )

    home, times, committed, created = await _single_homed_run(
        tmp_path, live, count=6_000, gap_s=0.004, timeout_delay=300,
        limit_s=120.0, seed=4, enough=enough,
    )
    assert set(times) == set(home), "a payload was lost"
    assert set(times.values()) == {1}, "a payload was committed twice"
    on_chain = {str(b.digest()) for b in committed}
    orphaned = [
        b for b in created.blocks if b[3] not in on_chain and b[2]
    ]
    relayed_orphans = {
        pid for author, _, ids, _ in orphaned for pid in ids
        if home[pid] != author
    }
    assert relayed_orphans, "no orphaned block carried a relayed digest"
    assert created.rebuffered >= 1
    assert all(times[pid] == 1 for pid in relayed_orphans)


@async_test
async def test_an_idle_committee_commits_a_lone_payload_without_a_timeout(
    tmp_path,
):
    """No block is being made: leader(1) holds a deferred Make.  A
    payload handed to node 4 reaches that leader at once and fires it;
    the round's relay would have waited for a processed block or a TC,
    one view-change timeout (20 s here) away."""
    nodes = await _committee_of_seven(tmp_path, range(N), timeout_delay=20_000)
    loop = asyncio.get_running_loop()
    try:
        await asyncio.sleep(0.3)  # every node is in round 1, nothing to do
        digest = Digest.of(b"relay|idle|0")
        began = loop.time()
        await nodes[4][0].tx_producer.put(digest)
        commits = nodes[0][1]
        while True:
            block = await asyncio.wait_for(commits.get(), 10.0)
            if digest in block.payloads:
                break
        assert loop.time() - began < 10.0
        assert block.round == 1 and block.author == keys(N)[1][0]
        home = nodes[4][0].proposer
        assert (home.early_frames, home.carried_next) == (2, 1)
    finally:
        for stack, _, _ in nodes:
            await stack.shutdown()
        for _, _, store in nodes:
            store.close()
