"""Proposer: payload buffering, block creation, quorum-ACK back-pressure.

Parity target: reference ``Proposer`` (consensus/src/proposer.rs:17-186),
the fork's producer payload path: producer digests arriving from external
parties are buffered; on ``Make(round, qc, tc)`` one buffered digest
becomes the payload of a signed block that is reliable-broadcast to the
committee, looped back to the core, and ACK-awaited until 2f+1 stake —
the leader back-pressure control system (proposer.rs:115-131).

Redesigned buffering (round-2 fix for the burst-and-stall dynamics the
reference's scheme produces):

- The reference buffers payloads in per-round buckets keyed by the
  store's ``latest_round + 1`` *at arrival time* (proposer.rs:164-173) and
  drops whole buckets as rounds are processed.  Under load, rounds race
  ahead of payload arrival, each round discards an entire bucket after
  consuming one digest, the buffer empties, and the next leader
  "proposes nothing" (proposer.rs:74-78) — wedging the round for the
  full 5 s view-change timeout.  Measured effect in round 1: commits in
  ~5 ms bursts separated by 5 s stalls, 87 ms mean consensus latency.
  The bucket scheme also costs one store round-trip per arriving payload
  (the ``latest_round`` read), 50k queue hops/s at the target rate.
- Here: one FIFO (ordered map) with digest dedup and O(1) removal of
  committed payloads (core cleanup).  ``Make`` pops the oldest
  payload; if the buffer is empty the make is DEFERRED and fires the
  moment the next payload arrives (superseded by newer makes, dropped by
  cleanups for later rounds).  No store reads at all on the payload
  path; consensus paces itself to the payload arrival rate instead of
  spinning empty rounds into view changes.

Digest relay (ISSUE 27).  A client hands a payload to ONE node, its
home, and leaders rotate: left alone, a digest waits in its home's FIFO
for the home's turn, n/2 rounds on the median (6.3 s of a 6.8 s commit
latency at 64 nodes).  So the home hands the digest (never the body) to
the node that makes the next block:

- *When and to whom.*  At admission (ISSUE 34): as soon as a wake-up
  of the producer queue has been drained into the buffer, the digests
  it admitted go out in ONE best-effort frame a target
  (``wire.encode_relay``, at most ``MAX_PRODUCER_BATCH`` digests) to
  the makers of the next two blocks this node has not seen made:
  ``leader(r + 1)`` and ``leader(r + 2)`` after it saw block ``r``
  processed or made it, ``leader(R)`` and ``leader(R + 1)`` after a TC
  seated round ``R`` (``unmade_round``).  The first copy puts the
  digest into the very next block when it lands before that leader's
  Make; the second is there a round ahead of the next one's, however
  slow the link.  A deferred Make fires on the copy, so an idle
  committee needs no timeout to commit a lone payload.  *Once a round
  besides, the backstop:* right after the core processed the round's
  block (a ``Cleanup`` carrying ``block``) or entered the round by a TC
  (``tc_entered``), the home sends what it admitted and still holds in
  ``pending`` to ``leader(round + 1)``, the node its vote goes to,
  but not a digest that leader has been sent already (``relayed_to``:
  digest -> the highest round whose leader has it): a digest costs two
  frames at admission and one more a round only while it is still
  uncarried after both.  In both cases nothing is sent when this node
  leads one of the rounds in question (the pair at admission; the next
  two, and the TC's own, once a round: it proposes them itself; only
  digests an orphaned block has carried still go, or a leader whose
  blocks always orphan, the one before a dead node, would propose and
  lose them every rotation), never to this node itself, and
  relayed-in digests are never relayed on.  The decision reads the
  elector and the rounds alone; there is no option.
- *Exactly once.*  A digest now sits in several buffers, so two rules
  keep it out of two blocks of one chain.  (1) Prune at processing:
  the payloads of every block the core processes, anyone's, leave
  ``pending`` and are tracked in ``inflight`` (round -> digests) until
  the chain commits through that round; a relayed digest that is in a
  tracked block or recently committed is refused on arrival (a copy
  that lands after its leader's Make stays in that leader's buffer
  until the node processes the block that carries it).  Orphans
  (the chain committed past the round without them) return to the
  FRONT of their HOME's buffer only, which relays them again.  (2) No
  payloads on an unseen parent: a ``Make`` whose ``qc.hash`` names a
  block whose processing this proposer has not seen takes no payloads;
  it proposes empty when ``allow_empty`` holds and otherwise waits for
  that block's message.  ``Make`` and the processed-block message
  travel on one queue, so their order is the core's order.
- *What it tells.*  Span ``ingest.relay`` (one a frame sent, one a
  frame received) and one ``Proposer stats:`` line a node every 5 s;
  on it ``early_frames=`` (relay frames sent at admission, of
  ``relay_frames=``) and ``carried_next=`` (this node's clients'
  payloads first carried by the block that was the next to be made
  when they were admitted, of ``wait_n=``).
"""

from __future__ import annotations

import asyncio
import logging
import os
from collections import OrderedDict

from ..crypto import Digest, PublicKey, SignatureService
from ..network import ReliableSender, SimpleSender
from ..telemetry import spans as _spans
from ..utils.clock import default_clock
from .config import Committee
from .core import ProposerMessage
from .messages import MAX_BLOCK_PAYLOADS, QC, TC, Block, Round
from .reconfig import ReconfigOp, newest_epoch
from .wire import MAX_PRODUCER_BATCH, encode_propose, encode_relay

log = logging.getLogger(__name__)

# Payload buffer bound: newest arrivals are dropped when full (the
# reference's bounded channel has the same drop-newest semantics).
MAX_PENDING = 100_000
# Dedup window: digests remembered (buffered or already proposed).
SEEN_CAP = 200_000
# In-flight proposal bound (rounds whose fate is undecided).  When commit
# signals stall past this many proposals, the OLDEST one's payloads are
# conservatively re-buffered (treated as orphaned).  The bound keeps
# inflight memory finite through arbitrarily long partitions; the
# eager re-buffer can duplicate a payload only if its commit signal is
# still unseen AFTER this many newer proposals resolved — and the
# committed_seen LRU (SEEN_CAP deep) still filters those on resolution.
MAX_INFLIGHT = 1_024
# Digests of processed blocks remembered for the unseen-parent rule: a
# Make builds on the newest QC's block, a few rounds old at most.
PROCESSED_CAP = 1_024
# One ``Proposer stats:`` line a node this often (seconds), beside the
# verify service's and the process's own.
STATS_EVERY_S = 5.0


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return max(1, int(raw))
    except ValueError:
        return default


class Proposer:
    def __init__(
        self,
        name: PublicKey,
        committee: Committee,
        signature_service: SignatureService,
        rx_producer: asyncio.Queue,
        rx_message: asyncio.Queue,
        tx_loopback: asyncio.Queue,
        network: ReliableSender | None = None,
        telemetry=None,
        adversary=None,
        admission=None,
        leader_elector=None,
        relay_network: SimpleSender | None = None,
    ):
        self.name = name
        # Digest relay (module docstring): who leads which round, and a
        # best-effort sender to reach the next leader with (the core's
        # own, so the frame travels on the connection the vote takes).
        # Either None (component tests construct the proposer bare):
        # nothing is relayed.
        self.leader_elector = leader_elector
        self.relay_network = relay_network
        # Ingest admission controller (ingest/admission.py): fed the
        # committed-payload counts from Cleanup messages — the drain
        # signal its credit window is derived from.  None = no ingest
        # plane (component tests construct the proposer bare).
        self.admission = admission
        # Buffer bound, overridable per run (HOTSTUFF_MAX_PENDING) so
        # load tests can shrink the buffer and reach the admission
        # watermark without queuing 100k payloads first.
        self.max_pending = _env_int("HOTSTUFF_MAX_PENDING", MAX_PENDING)
        # Payloads silently dropped at the full buffer — with admission
        # control active this staying at ZERO under overload is the
        # acceptance signal (sheds happen at the ingest door instead).
        self.drop_newest = 0
        # Byzantine adversary plane (faults/adversary.py): None on
        # honest nodes; the equivocation seam in _make_block consults it
        self.adversary = adversary
        self.committee = committee
        self.signature_service = signature_service
        self.rx_producer = rx_producer
        self.rx_message = rx_message
        self.tx_loopback = tx_loopback
        # FIFO with O(1) membership/removal: committed payloads are
        # pruned by digest on every commit (Core._commit cleanup).
        self.pending: OrderedDict[Digest, float | None] = OrderedDict()
        self.seen: OrderedDict[Digest, None] = OrderedDict()
        # Digests this node admitted from its own clients and that have
        # not committed: digest -> when it was admitted (monotonic s;
        # 0.0 once a processed block has carried it).  Only these are
        # relayed, and only these return to the buffer when a block
        # that carried them orphans: the home is the one node sure to
        # hold the digest, and its body.
        self.home: dict[Digest, float] = {}
        # Those of them an orphaned block carried and no block since:
        # relayed even while this node leads soon (module docstring).
        self.orphans: dict[Digest, None] = {}
        # Processed blocks whose fate is undecided, own or others':
        # round -> payloads.  If a block orphans (a view change built
        # the chain past it), its payloads must return to their home's
        # buffer or they are lost for good.  Resolved by commit signals
        # (cleanup messages carrying committed_round).
        self.inflight: dict[Round, tuple] = {}
        self._tracked_set: set | None = None  # ``_tracked()``, kept
        # Blocks whose processing this proposer has seen (its own count
        # from their making): what a Make may put payloads on.
        self.processed: OrderedDict[Digest, None] = OrderedDict()
        # newest round the once-a-round relay ran for / committed through
        self.relayed_round: Round = 0
        self.committed_round: Round = 0
        # The first round whose block this proposer has not seen made:
        # one past the newest block it made or saw processed, or the
        # round a TC has just seated.  A digest admitted now goes to
        # this round's leader and the next one's (module docstring).
        self.unmade_round: Round = 1
        # Home digests this wake-up of the producer queue has buffered:
        # relayed as soon as the queue is drained.
        self._admitted: list[Digest] = []
        # Home digest -> the highest round whose leader was sent it (the
        # round's relay does not send a digest to the same target
        # twice); dropped where ``home`` drops it.
        self.relayed_to: dict[Digest, Round] = {}
        # Home digest -> ``unmade_round`` when it was admitted, until a
        # block first carries it (``carried_next``).
        self.admitted_before: dict[Digest, Round] = {}
        # The ``Proposer stats`` line's counters, all cumulative.
        self.relayed_digests = 0  # digests sent in relay frames
        self.relay_frames = 0  # relay frames sent
        self.proposed_relayed = 0  # payloads proposed for another home
        self.proposed_home = 0  # payloads proposed for our own clients
        self.wait_s_sum = 0.0  # admitted here -> in a processed block
        self.wait_count = 0
        self.early_frames = 0  # relay frames sent at admission
        self.carried_next = 0  # first carried by the block made next
        self._next_stats = 0.0
        # Recently COMMITTED digests (bounded LRU): orphan recovery must
        # not re-buffer a payload that committed in an EARLIER walk via
        # another node's block (multi-homed producers) — the per-walk
        # payload set alone cannot show that.
        self.committed_seen: OrderedDict[Digest, None] = OrderedDict()
        self.deferred: ProposerMessage | None = None
        # A core-validated reconfiguration op awaiting our next leader
        # slot (docs/RECONFIG.md); dropped once its epoch is scheduled
        # (another leader's block carried it first).
        self.pending_reconfig: ReconfigOp | None = None
        # Highest round a block was actually created for: re-issued Makes
        # for the same round are dropped, so (a) the core may safely
        # re-send a Make when allow_empty conditions change, and (b) this
        # node can never produce two blocks for one round (leader
        # equivocation guard).
        self.last_made_round: Round = 0
        self.network = network if network is not None else ReliableSender()
        self._task: asyncio.Task | None = None
        self.log = logging.getLogger(f"{__name__}.{str(name)[:8]}")
        self._node = str(name)[:8]  # the ``node`` id of its spans
        # Telemetry (optional): payload buffer dwell time + buffer
        # occupancy.  With telemetry on, `pending` values hold the
        # arrival timestamp (read at make time); off, they stay None —
        # no per-payload float allocation.
        self.telemetry = telemetry
        self._payload_wait = None
        self._deferred_makes = None
        self._journal = telemetry.journal if telemetry is not None else None
        if telemetry is not None:
            self._payload_wait = telemetry.trace.payload_wait
            self._deferred_makes = telemetry.counter(
                "proposer_deferred_makes",
                "Makes deferred for lack of buffered payloads",
            )
            telemetry.gauge(
                "proposer_pending_payloads",
                "Payload digests buffered for proposal",
                fn=lambda: len(self.pending),
            )
            telemetry.gauge(
                "proposer_inflight_proposals",
                "Own proposals whose commit/orphan fate is undecided",
                fn=lambda: len(self.inflight),
            )
            for gauge, help_text, attr in (
                ("proposer_relayed_digests",
                 "Payload digests sent to the next leader", "relayed_digests"),
                ("proposer_relay_frames",
                 "Relay frames sent to the next leader", "relay_frames"),
                ("proposer_proposed_relayed",
                 "Payloads proposed here for another node's clients",
                 "proposed_relayed"),
                ("proposer_proposed_home",
                 "Payloads proposed here for this node's own clients",
                 "proposed_home"),
            ):
                telemetry.gauge(
                    gauge, help_text, fn=lambda a=attr: getattr(self, a)
                )
            telemetry.gauge(
                "proposer_drop_newest",
                "Payloads silently dropped at the full buffer "
                "(admission control should keep this at zero)",
                fn=lambda: self.drop_newest,
            )

    def _buffer_payload(self, digest: Digest, home: bool = True) -> None:
        if digest in self.seen:
            return  # duplicate of a buffered or recently proposed payload
        if len(self.pending) >= self.max_pending:
            self.drop_newest += 1
            return  # drop newest under overload (bounded like reference)
        self.seen[digest] = None
        while len(self.seen) > SEEN_CAP:
            self.seen.popitem(last=False)
        now = default_clock().monotonic()
        if home:
            self.home[digest] = now
            self.admitted_before[digest] = self.unmade_round
            self._admitted.append(digest)
        self.pending[digest] = now

    def _buffer_item(self, item) -> None:
        """One item of the producer queue: a client's digest this node
        admitted (it is the digest's home), or a peer's relay frame, a
        tuple of digests that peer admitted."""
        if type(item) is not tuple:
            self._buffer_payload(item)
            return
        # the frame may be older than the block that carried its
        # digests: anything in a tracked block or recently committed
        # must not enter the buffer again
        tracked = self._tracked()
        for digest in item:
            if digest not in tracked and digest not in self.committed_seen:
                self._buffer_payload(digest, home=False)

    async def _make_block(
        self, round_: Round, qc: QC, tc: TC | None, allow_empty: bool = False
    ) -> None:
        with _spans.span("proposer.make", node=self._node, round=round_):
            if round_ <= self.last_made_round:
                return  # already proposed for this round (equivocation guard)
            op = self.pending_reconfig
            if op is not None and newest_epoch(self.committee) >= op.new_committee.epoch:
                # the epoch change is already scheduled (committed via
                # another leader's block, or a competing op won): drop ours
                self.pending_reconfig = None
                op = None
            snipes = (
                self.adversary.wants("reconfig", round_)
                if op is None and self.adversary is not None else False
            )
            if snipes:
                # reconfig policy (forge half): attach a forged epoch change
                # — well-formed wire, hostile committee / bad sponsor — that
                # MUST die in every honest voter's Block.verify.  The
                # reconfig-sniper mounts the same forgery, but only inside
                # the epoch-activation margin (wants returns its token).
                op = self.adversary.forged_reconfig(self.committee, round_)
                if op is not None:
                    self.adversary.mark_adaptive(snipes, round_, self.log)
                    self.adversary.count("byz_forged_reconfigs")
                    self.adversary.record("reconfig-forge", round_)
                    self.log.info("byz reconfig-forge round %d", round_)
            # No payloads on an unseen parent (module docstring): votes
            # can overtake the proposal, and the block they certify may
            # carry digests that are still in this buffer.
            parent_seen = self._parent_seen(qc)
            if (
                (not self.pending or not parent_seen)
                and not allow_empty
                and op is None
            ):
                # Defer: fire the moment the next payload (or the parent's
                # processed-block message) arrives instead of wedging the
                # round until the view-change timer (see module docstring).
                # A newer Make supersedes this one.
                self.deferred = ProposerMessage.make(round_, qc, tc)
                if self._deferred_makes is not None:
                    self._deferred_makes.inc()
                self.log.info(
                    "Round: %d, %s - proposal deferred", round_,
                    "no payloads yet" if parent_seen
                    else "parent not processed yet",
                )
                return
            # allow_empty: the core signalled that uncommitted payload blocks
            # are in flight — an empty block advances the 2-chain so they
            # commit now rather than on the producer's next burst.
            self.last_made_round = round_
            self.unmade_round = max(self.unmade_round, round_ + 1)
            take = min(len(self.pending), MAX_BLOCK_PAYLOADS) if parent_seen else 0
            popped = [self.pending.popitem(last=False) for _ in range(take)]
            payloads = tuple(d for d, _ in popped)
            if popped:
                now = default_clock().monotonic()
                if self._payload_wait is not None:
                    for _, arrived in popped:
                        if arrived:  # re-buffered orphans may carry None
                            self._payload_wait.observe(now - arrived)
                ours = sum(self._carried(d, now, round_) for d in payloads)
                self.proposed_home += ours
                self.proposed_relayed += take - ours

            if op is not None and op is self.pending_reconfig:
                self.pending_reconfig = None  # it rides in this block
            block = Block(
                qc=qc, tc=tc, author=self.name, round=round_, payloads=payloads,
                reconfig=op,
            )
            digest = block.digest()
            # our own block counts as processed from here on: its
            # payloads have left the buffer, and the core's message for
            # it finds it tracked already
            self._track(block)
        block.signature = await self.signature_service.request_signature(digest)
        with _spans.span("proposer.make", node=self._node, round=round_):
            if op is not None:
                self.log.info(
                    "Proposing reconfig in block %d: epoch %d (margin %d)",
                    round_, op.new_committee.epoch, op.margin,
                )
            # NOTE: this log entry is used to compute performance — the harness
            # maps each payload -> block digest from it (benchmark/logs.py
            # contract).
            self.log.info(
                "Created block %d (payloads %s) -> %s",
                block.round,
                ",".join(str(p) for p in block.payloads),
                block.digest(),
            )
            if self._journal is not None:
                # the propose record is the timeline anchor traces.py hangs
                # every recv.propose edge off — journaled just before the
                # broadcast leaves this node
                self._journal.record("propose", block.round, block.digest())
                if block.payloads:
                    # producer-channel edge (ROADMAP PR 2 follow-up): pairs
                    # with the receiver's recv.producer record so traces
                    # can measure payload-wait (client frame -> proposed)
                    # and chaos runs can tell payload starvation from
                    # consensus stall
                    self._journal.record(
                        "payload.first", block.round, block.payloads[0]
                    )

            # Broadcast to the union of epochs (committee.broadcast_addresses
            # is the union on a CommitteeSchedule — members of the adjacent
            # epoch need boundary blocks too); ACK stake counts only under
            # the BLOCK round's committee.
            com = self.committee.for_round(round_)
            names_addresses = self.committee.broadcast_addresses(self.name)
            message = encode_propose(block)
        # broadcast() (not a per-peer send loop) so flow accounting
        # charges ONE logical propose per proposal: the wire/logical
        # ratio is the leader amplification factor (== n-1 here).
        # ReliableSender.broadcast enqueues per address in list order,
        # so handles pair with names exactly as the loop did.
        handles = list(
            zip(
                (name for name, _ in names_addresses),
                await self.network.broadcast(
                    [address for _, address in names_addresses], message
                ),
            )
        )

        await self.tx_loopback.put(block)

        ambushes = (
            self.adversary.wants("equivocate", block.round)
            if self.adversary is not None else False
        )
        if ambushes:
            # schedule-driven equivocation, or the ambush-leader trigger
            # (faults/adaptive.py): equivocate exactly when we lead a
            # round seated by a fresh TC
            self.adversary.mark_adaptive(
                ambushes, block.round, self.log, block.digest()
            )
            await self._byz_equivocate(block, names_addresses)

        # Control system: wait for 2f+1 total stake (ours included) to ACK
        # the block before making the next one.
        total_stake = com.stake(self.name)
        threshold = com.quorum_threshold()
        # tasks is an ordered LIST (committee order), not a set:
        # cancelling a waiter propagates into its ACK handle, which the
        # reliable sender reads as "give up retransmitting this frame" —
        # id()-ordered set iteration here made the surviving retransmit
        # set depend on heap layout (caught by the deterministic sim's
        # byte-identical-journal check).
        tasks = [
            asyncio.ensure_future(self._ack_stake(handle, com.stake(name)))
            for name, handle in handles
        ]
        pending = set(tasks)
        try:
            while pending and total_stake < threshold:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED
                )
                for t in done:
                    # lint: allow(no-blocking-in-async) -- t is in the
                    # done set asyncio.wait just returned: result() is
                    # an immediate read, never a block
                    total_stake += t.result()
        finally:
            for t in tasks:
                if not t.done():
                    t.cancel()

    async def _byz_equivocate(self, block: Block, names_addresses) -> None:
        """equivocate policy (adversary plane): sign the deterministic
        shadow twin of the block just proposed — same round, same QC,
        conflicting payloads — and ship it to a deterministic peer
        subset (fellow colluders when colluding, else the first half of
        the peer set).  Honest receivers vote at most once per round,
        so the main branch keeps committing; the checker attributes the
        equivocations to this authority."""
        adversary = self.adversary
        shadow = adversary.shadow_block(block)
        shadow.signature = await self.signature_service.request_signature(
            shadow.digest()
        )
        targets = adversary.equivocation_targets(names_addresses)
        message = encode_propose(shadow)
        for _, address in targets:
            await self.network.send(address, message)
        adversary.count("byz_equivocations")
        adversary.record(
            "equivocate", block.round, shadow.digest(), f"{len(targets)}p"
        )
        self.log.info(
            "byz equivocate round %d -> %s | %s (%d peers)",
            block.round, block.digest(), shadow.digest(), len(targets),
        )

    def _parent_seen(self, qc: QC) -> bool:
        return qc.is_genesis() or qc.hash in self.processed

    def _carried(self, digest: Digest, now: float, round_: Round) -> bool:
        """Block ``round_``, which this proposer made or saw processed,
        carries ``digest``.  True if this node is its home; the first
        time, that ends its wait (admitted here -> in a processed
        block)."""
        admitted = self.home.get(digest)
        if admitted is None:
            return False
        if admitted:
            self.wait_s_sum += now - admitted
            self.wait_count += 1
            self.home[digest] = 0.0
            if self.admitted_before.pop(digest, None) == round_:
                self.carried_next += 1
        else:
            self.orphans.pop(digest, None)
        return True

    def _track(self, block: Block) -> bool:
        """Remember a processed block (or one just made here) until the
        chain commits through its round.  False if it is tracked
        already, or too old to matter."""
        digest = block.digest()
        if digest in self.processed:
            return False
        self.processed[digest] = None
        while len(self.processed) > PROCESSED_CAP:
            self.processed.popitem(last=False)
        if block.round <= self.committed_round:
            # a block below the commit cursor (a late sync reply): if
            # it is on the chain its payloads were pruned at commit, if
            # it is not it decides nothing
            return False
        if block.payloads:
            # an equivocating leader's twin shares its round
            self.inflight[block.round] = (
                self.inflight.get(block.round, ()) + block.payloads
            )
            self._tracked_set = None
            while len(self.inflight) > MAX_INFLIGHT:
                self._requeue_oldest_inflight()
        return True

    def _tracked(self) -> set:
        """Every digest a tracked block carries: a few rounds' worth,
        gathered when a relay frame arrives or a block orphans and kept
        until ``inflight`` changes (a leader takes some seventy frames
        between two blocks), not kept up on every node for every
        block."""
        if self._tracked_set is None:
            self._tracked_set = set().union(*self.inflight.values())
        return self._tracked_set

    def _untrack(self, round_: Round) -> tuple:
        self._tracked_set = None
        return self.inflight.pop(round_)

    def _on_processed(self, block: Block) -> None:
        """Prune at processing: the core processed ``block`` (anyone's),
        so its payloads leave the buffer now, two rounds before they
        commit, and a leader that builds on it cannot propose them
        again."""
        if not self._track(block):
            return
        # 64 nodes do this for every block: most hold none of its
        # payloads, and a miss costs a hash
        pending, home = self.pending, self.home
        if pending:
            for d in block.payloads:
                pending.pop(d, None)
        if home:
            ours = [d for d in block.payloads if d in home]
            if ours:
                now = default_clock().monotonic()
                for d in ours:
                    self._carried(d, now, block.round)

    async def _relay(self, round_: Round, made: bool) -> None:
        """The round's relay, the backstop of the one at admission: hand
        this node's own clients' digests that are still buffered to the
        node that makes block ``round_ + 1`` (module docstring).
        ``made``: block ``round_`` exists already (the call follows its
        processing); after a TC it does not, and its leader's Make is
        still behind this message in the queue."""
        unmade = round_ + made
        self.unmade_round = max(self.unmade_round, unmade)
        if self.leader_elector is None or self.relay_network is None:
            return
        if round_ <= self.relayed_round:
            return
        self.relayed_round = round_
        if self.home:
            await self._send_relay(
                self.home, range(unmade, round_ + 3), (round_ + 1,)
            )

    async def _relay_admitted(self) -> None:
        """The relay at admission: what this wake-up of the producer
        queue admitted goes at once to the makers of the next two blocks
        this node has not seen made (module docstring)."""
        admitted = self._admitted
        if not admitted:
            return
        self._admitted = []
        if self.leader_elector is None or self.relay_network is None:
            return
        pair = range(self.unmade_round, self.unmade_round + 2)
        await self._send_relay(admitted, pair, pair, early=True)

    async def _send_relay(
        self, ours, soon: range, targets, early: bool = False
    ) -> None:
        """One best-effort frame to the leader of each round in
        ``targets`` with the digests of ``ours`` that are still buffered
        and that this leader has not been sent yet.  When this node
        leads one of the rounds ``soon`` they ride in its own block,
        and only what an orphaned block has carried goes."""
        leader = self.leader_elector.get_leader
        if any(leader(r) == self.name for r in soon):
            ours = self.orphans
        if not ours:
            return
        pending, relayed_to = self.pending, self.relayed_to
        for target in targets:
            if leader(target) == self.name:
                continue
            digests = []
            for d in ours:
                if d in pending and relayed_to.get(d, 0) < target:
                    digests.append(d)
                    if len(digests) == MAX_PRODUCER_BATCH:
                        break
            if not digests:
                continue
            with _spans.span("ingest.relay", node=self._node, round=target):
                address = self.committee.for_round(target).address(
                    leader(target)
                )
                if address is None:
                    continue
                frame = encode_relay(digests)
                self.relay_frames += 1
                self.early_frames += early
                self.relayed_digests += len(digests)
                for d in digests:
                    relayed_to[d] = target
            await self.relay_network.send(address, frame)

    def _log_stats(self) -> None:
        now = default_clock().monotonic()
        if now < self._next_stats:
            return
        self._next_stats = now + STATS_EVERY_S
        # NOTE: this log entry is used to compute performance
        # (chipbench/readers/proposerstats.py): cumulative counters, a
        # reader takes last less first.
        self.log.info(
            "Proposer stats: relayed=%d relay_frames=%d proposed_relayed=%d "
            "proposed_home=%d wait_ms_sum=%.1f wait_n=%d early_frames=%d "
            "carried_next=%d",
            self.relayed_digests,
            self.relay_frames,
            self.proposed_relayed,
            self.proposed_home,
            self.wait_s_sum * 1e3,
            self.wait_count,
            self.early_frames,
            self.carried_next,
        )

    def _requeue_orphans(
        self, round_: Round, payloads: tuple, committed=frozenset(), note: str = ""
    ) -> None:
        """Re-buffer a resolved/abandoned block's payloads at the
        FRONT of the queue (oldest-first order preserved by callers
        iterating newest-round-first): those this node is the home of,
        skipping anything known committed, carried by another tracked
        block, or already buffered."""
        orphaned = [
            d for d in payloads
            if d in self.home
            and d not in committed
            and d not in self.committed_seen
            and d not in self.pending
        ]
        if orphaned:
            tracked = self._tracked()  # without this block: it was popped
            orphaned = [d for d in orphaned if d not in tracked]
        if orphaned:
            self.log.info(
                "Re-buffering %d payloads from %s block %d",
                len(orphaned),
                note or "orphaned",
                round_,
            )
        for digest in reversed(orphaned):
            self.pending[digest] = None
            self.pending.move_to_end(digest, last=False)
        self.orphans.update(dict.fromkeys(orphaned))

    def _requeue_oldest_inflight(self) -> None:
        """Inflight overflow (MAX_INFLIGHT): re-buffer the oldest
        undecided proposal's payloads as if orphaned.  Single-homed
        payloads survive the stall; the committed_seen/pending filters
        keep the duplicate window bounded (see MAX_INFLIGHT note)."""
        round_ = min(self.inflight)
        self._requeue_orphans(round_, self._untrack(round_), note="unresolved")

    def _resolve_inflight(self, message: ProposerMessage) -> None:
        """Orphan recovery: once the chain is committed through round R,
        every tracked block at round <= R either committed (its
        payloads are in the accumulated committed sets) or was orphaned
        by a view change — re-buffer our clients' orphans at the FRONT
        of the queue (oldest first) so single-homed payloads are never
        lost."""
        if not message.committed_round:
            return
        self.committed_round = max(self.committed_round, message.committed_round)
        for round_ in sorted(
            (r for r in self.inflight if r <= message.committed_round),
            reverse=True,  # re-insert newest first so oldest ends up in front
        ):
            self._requeue_orphans(
                round_, self._untrack(round_), committed=message.payloads
            )

    @staticmethod
    async def _ack_stake(handle: asyncio.Future, stake: int) -> int:
        # handle resolves with the peer's ACK; deliver that peer's stake
        await handle
        return stake

    async def run(self) -> None:
        prod_task = asyncio.ensure_future(self.rx_producer.get())
        msg_task = asyncio.ensure_future(self.rx_message.get())
        try:
            while True:
                done, _ = await asyncio.wait(
                    {prod_task, msg_task}, return_when=asyncio.FIRST_COMPLETED
                )
                if prod_task in done:
                    # lint: allow(no-blocking-in-async) -- guarded by
                    # membership in asyncio.wait's done set
                    item = prod_task.result()
                    with _spans.span("ingest.buffer", node=self._node):
                        self._buffer_item(item)
                        # drain any burst backlog without extra loop passes
                        while not self.rx_producer.empty():
                            self._buffer_item(self.rx_producer.get_nowait())
                    prod_task = asyncio.ensure_future(self.rx_producer.get())
                    await self._relay_admitted()
                    make = self.deferred
                    if (
                        make is not None
                        and self.pending
                        and self._parent_seen(make.qc)
                    ):
                        self.deferred = None
                        await self._make_block(make.round, make.qc, make.tc)
                if msg_task in done:
                    # lint: allow(no-blocking-in-async) -- guarded by
                    # membership in asyncio.wait's done set
                    message: ProposerMessage = msg_task.result()
                    if message.kind == ProposerMessage.MAKE:
                        self.deferred = None  # superseded
                        await self._make_block(
                            message.round,
                            message.qc,
                            message.tc,
                            message.allow_empty,
                        )
                    elif message.kind == ProposerMessage.RECONFIG:
                        self.pending_reconfig = message.op
                        self.log.info(
                            "Reconfig op buffered for the next leader "
                            "slot: epoch %d",
                            message.op.new_committee.epoch,
                        )
                        if self.deferred is not None:
                            # an empty-buffer make was parked waiting
                            # for payloads — the op is reason enough to
                            # propose now
                            make = self.deferred
                            self.deferred = None
                            await self._make_block(make.round, make.qc, make.tc)
                    else:
                        with _spans.span("proposer.cleanup", node=self._node):
                            # Cleanup(rounds): the chain advanced through these
                            # rounds — a deferred make for an older round is
                            # stale (the core will issue a fresh Make when this
                            # node next leads).
                            if (
                                self.deferred is not None
                                and message.rounds
                                and self.deferred.round <= max(message.rounds)
                            ):
                                self.deferred = None
                            # Cleanup(payloads): these digests committed (in
                            # anyone's block) — proposing them again would
                            # waste block capacity on duplicates.  They stay
                            # in `seen` so a re-delivered copy is not
                            # re-buffered either.
                            if self.admission is not None and message.payloads:
                                # drain signal for the ingest credit window
                                self.admission.on_committed(len(message.payloads))
                            home = self.home
                            for digest in message.payloads:
                                self.pending.pop(digest, None)
                                self.committed_seen[digest] = None
                                if home and home.pop(digest, None) is not None:
                                    self.orphans.pop(digest, None)
                                    self.relayed_to.pop(digest, None)
                                    self.admitted_before.pop(digest, None)
                            while len(self.committed_seen) > SEEN_CAP:
                                self.committed_seen.popitem(last=False)
                            self._resolve_inflight(message)
                            block = message.block
                            if block is not None:
                                self._on_processed(block)
                                self._log_stats()
                        # the round's relay: after its block was pruned
                        # from the buffer, or on the TC that brought no
                        # block (an older block, a sync reply, is behind
                        # relayed_round and sends nothing)
                        if block is not None:
                            await self._relay(block.round, made=True)
                        elif message.tc_entered:
                            await self._relay(message.tc_entered, made=False)
                        make = self.deferred
                        if (
                            make is not None
                            and block is not None
                            and self.pending
                            and self._parent_seen(make.qc)
                        ):
                            # the parent a Make was waiting for
                            self.deferred = None
                            await self._make_block(make.round, make.qc, make.tc)
                    msg_task = asyncio.ensure_future(self.rx_message.get())
        finally:
            prod_task.cancel()
            msg_task.cancel()

    def spawn(self) -> asyncio.Task:
        self._task = asyncio.get_running_loop().create_task(
            self.run(), name="proposer"
        )
        return self._task

    def shutdown(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
        self.network.close()
