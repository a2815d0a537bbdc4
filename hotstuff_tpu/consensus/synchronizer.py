"""Synchronizer: parent-block fetch and re-injection.

Parity target: reference ``Synchronizer`` (consensus/src/synchronizer.rs:
24-149). ``get_parent_block`` answers from the store, or — on a miss —
hands the orphan block to an inner task that (a) sends a SyncRequest to the
block's author, (b) parks a waiter on ``store.notify_read(parent)``, and
(c) re-broadcasts requests older than ``sync_retry_delay`` to the whole
committee every TIMER_ACCURACY tick (the "perfect point-to-point link"
retry, synchronizer.rs:84-105). When the parent is finally written, the
suspended child block is re-sent to the core via the loopback channel.

Beyond the reference: requests EXPIRE.  A parent digest that never
arrives (equivocating proposer, or a sender partitioned before anyone
stored the block) used to pin its waiter task, its ``_pending`` /
``_requests`` entries, and its store obligation forever, while
re-broadcasting to the whole committee every retry tick.  After
``sync_giveup`` seconds the request is abandoned: waiters are
cancelled, the suspended children are forgotten (a live chain re-sends
them via a later QC), and the store obligation is dropped.

Also beyond the reference: the synchronizer KEEPS the last few blocks
its core stored (``keep``), decoded, and ``get_parent_block`` answers
from them before it reads the store.  The two ancestors of a block are
the blocks the node processed one and two rounds ago; decoding each
again out of the store, 43-vote QC and all, was the largest span of a
64-node round.
"""

from __future__ import annotations

import asyncio
import logging

from ..crypto import Digest, PublicKey
from ..network import SimpleSender
from ..store import Store
from ..telemetry import spans as _spans
from ..utils.clock import default_clock
from .config import Committee
from .errors import SerializationError
from .messages import Block
from .wire import encode_sync_request

log = logging.getLogger(__name__)

TIMER_ACCURACY_S = 5.0

#: decoded blocks a synchronizer keeps: the 2-chain asks for two, a
#: commit walk after a view change for a few more
KEPT_BLOCKS = 8


class AncestorCounts:
    """Parent lookups answered from a synchronizer's kept blocks (hits)
    and those that went on to the store (misses), and the parent
    requests sent to peers (first asks and each peer of a retry
    broadcast), over every node of the process since it started: the
    ``ancestor_hits=``, ``ancestor_misses=`` and ``sync_requests=`` of
    the ``Host stats:`` line (``telemetry/hoststats.py``)."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.sync_requests = 0


#: the process's one count, as ``store/engine.py`` ``WAL_COUNTS`` is
ANCESTOR_COUNTS = AncestorCounts()


class Synchronizer:
    def __init__(
        self,
        name: PublicKey,
        committee: Committee,
        store: Store,
        tx_loopback: asyncio.Queue,
        sync_retry_delay_ms: int,
        network: SimpleSender | None = None,
        telemetry=None,
    ):
        self.name = name
        self.committee = committee
        self.store = store
        self.tx_loopback = tx_loopback
        self.sync_retry_delay = sync_retry_delay_ms / 1000.0
        self.network = network if network is not None else SimpleSender()
        self._journal = telemetry.journal if telemetry is not None else None

        self.log = logging.getLogger(f"{__name__}.{str(name)[:8]}")
        self._node = str(name)[:8]  # the ``node`` id of its spans
        self._pending: set[Digest] = set()  # child digests being synced
        # parent digest -> (first-ask time, child round, parent round):
        # the rounds make the retry broadcast epoch-targeted
        self._requests: dict[Digest, tuple[float, int, int]] = {}
        # Epoch-aware join barrier: set (by the state-sync client) to
        # the snapshot adoption round on a certified-schedule join —
        # ancestry below it is covered by the snapshot and must never
        # be fetched, whatever floor a caller passes.
        self.join_floor = 0
        # digest -> the Block object the core stored under it, the
        # newest KEPT_BLOCKS of them (``keep``).  A subset of the store,
        # never ahead of it, and owned by this incarnation of the node:
        # a restart (a new process, or the sim plane's crash/restart)
        # builds a new Synchronizer, so it starts empty and ancestry is
        # read from what the store recovered.
        self._kept: dict[Digest, Block] = {}
        self._waiters: set[asyncio.Task] = set()
        # give-up bookkeeping: which waiters/children each parent pins
        self._by_parent: dict[Digest, list[asyncio.Task]] = {}
        self._children: dict[Digest, set[Digest]] = {}
        # generous: far past any honest delivery, but bounded (a parent
        # that never arrives must not leak tasks or spam the committee)
        self.sync_giveup = max(30.0, 20 * self.sync_retry_delay)
        self.expired = 0  # abandoned requests (telemetry gauge)
        self._retry_task: asyncio.Task | None = None

    def _ensure_retry_task(self) -> None:
        if self._retry_task is None or self._retry_task.done():
            self._retry_task = asyncio.get_running_loop().create_task(
                self._retry_loop(), name="synchronizer-retry"
            )

    async def _retry_loop(self) -> None:
        while True:
            await default_clock().sleep(TIMER_ACCURACY_S)
            now = default_clock().monotonic()
            for digest, (asked_at, child_round, parent_round) in list(
                self._requests.items()
            ):
                if asked_at + self.sync_giveup < now:
                    self._expire(digest)
                elif asked_at + self.sync_retry_delay < now:
                    self.log.debug("Requesting sync for block %s (retry)", digest)
                    addresses = self._sync_targets(child_round, parent_round)
                    message = encode_sync_request(digest, self.name)
                    ANCESTOR_COUNTS.sync_requests += len(addresses)
                    await self.network.broadcast(addresses, message)

    def _sync_targets(self, child_round: int, parent_round: int) -> list:
        """Retry-broadcast targets for a missing parent: the members of
        the child's epoch plus the parent's epoch (they differ exactly
        at a reconfiguration boundary — the retiring members are the
        ones guaranteed to hold the old-epoch block).  The all-epoch
        union would instead spam every past epoch's membership on each
        retry tick."""
        seen: dict = {}
        for r in (child_round, max(1, parent_round)):
            com = self.committee.for_round(r)
            for nm, addr in com.broadcast_addresses(self.name):
                seen.setdefault(nm, addr)
        return list(seen.values())

    def _expire(self, parent: Digest) -> None:
        """Abandon a parent that never arrived: unpin everything it
        holds.  The chain self-heals if the digest was real — a later
        block certifying it re-enters via get_parent_block."""
        self.expired += 1
        self._requests.pop(parent, None)
        for task in self._by_parent.pop(parent, ()):
            task.cancel()
        for child in self._children.pop(parent, ()):
            self._pending.discard(child)
        self.store.cancel_notify(parent.to_bytes())
        if self._journal is not None:
            self._journal.record("sync.expire", 0, parent)
        self.log.warning(
            "Giving up sync for parent %s after %.0fs", parent, self.sync_giveup
        )

    async def _waiter(self, parent: Digest, child: Block) -> None:
        """Park on the store until the parent exists, then loop the child
        block back into the core (synchronizer.rs:74-83, 115-118)."""
        try:
            await self.store.notify_read(parent.to_bytes())
        except asyncio.CancelledError:
            return
        self._pending.discard(child.digest())
        self._requests.pop(parent, None)
        if self._journal is not None:
            self._journal.record("sync.done", child.round, parent)
        await self.tx_loopback.put(child)

    async def _request_parent(self, block: Block) -> None:
        if block.digest() in self._pending:
            return
        self._pending.add(block.digest())
        parent = block.parent
        task = asyncio.get_running_loop().create_task(
            self._waiter(parent, block), name=f"sync-wait-{parent}"
        )
        self._waiters.add(task)
        self._by_parent.setdefault(parent, []).append(task)
        self._children.setdefault(parent, set()).add(block.digest())

        def _cleanup(t, parent=parent):
            self._waiters.discard(t)
            tasks = self._by_parent.get(parent)
            if tasks is not None:
                try:
                    tasks.remove(t)
                except ValueError:
                    pass
                if not tasks:
                    self._by_parent.pop(parent, None)
                    self._children.pop(parent, None)

        task.add_done_callback(_cleanup)

        if parent not in self._requests:
            self.log.debug("Requesting sync for block %s", parent)
            self._requests[parent] = (
                default_clock().monotonic(), block.round, block.qc.round
            )
            if self._journal is not None:
                self._journal.record(
                    "sync.req", block.round, parent, str(block.author)[:8]
                )
            address = self.committee.address(block.author)
            if address is not None:
                ANCESTOR_COUNTS.sync_requests += 1
                await self.network.send(
                    address, encode_sync_request(parent, self.name)
                )
        self._ensure_retry_task()

    def keep(self, block: Block) -> None:
        """Keep ``block`` for ``get_parent_block``.  The core calls this
        once the block's store write has returned, and nothing else
        does: what is kept is in the log.  Blocks are immutable after
        construction (messages.py), so the kept object is the one a
        decode of the stored bytes would rebuild."""
        kept, digest = self._kept, block.digest()
        kept.pop(digest, None)  # kept again: the newest now
        kept[digest] = block
        if len(kept) > KEPT_BLOCKS:
            del kept[next(iter(kept))]

    async def get_parent_block(
        self, block: Block, floor: int = -1
    ) -> Block | None:
        """The block certified by ``block.qc``, from the kept blocks or
        else decoded from the store; None if it must be fetched (in which
        case processing of ``block`` is suspended).

        ``floor`` is the snapshot barrier: a node that adopted a
        QC-anchored state snapshot holds no block history at or below its
        commit cursor, and that history must never be fetched — otherwise
        a snapshot rejoin degenerates into the hop-by-hop ancestry
        backfill the snapshot exists to skip (and stalls outright when an
        old proposer is unreachable).  A missing parent certified at or
        below the floor resolves to the genesis stand-in: the block's own
        verified QC vouches for it, its state effects are inside the
        snapshot, and callers only read ``.round`` from it (the 2-chain
        commit rule can never fire across the cut)."""
        if block.qc.is_genesis():
            return Block.genesis()
        # a miss opens the span twice, around the lookup and around the
        # decode: the store read between them is an ``await`` (lint rule
        # no-await-in-span) and has the ``store.read`` span of its own
        with _spans.span("core.ancestors", node=self._node):
            kept = self._kept.get(block.parent)
            if kept is not None:
                ANCESTOR_COUNTS.hits += 1
                return kept
            ANCESTOR_COUNTS.misses += 1
        data = await self.store.read(block.parent.to_bytes())
        if data is not None:
            with _spans.span("core.ancestors", node=self._node):
                try:
                    return Block.deserialize(data)
                except Exception as e:
                    raise SerializationError(
                        f"corrupt block in store: {e}"
                    ) from e
        if block.qc.round <= max(floor, self.join_floor):
            return Block.genesis()
        await self._request_parent(block)
        return None

    async def get_ancestors(
        self, block: Block, floor: int = -1
    ) -> tuple[Block, Block] | None:
        """(b0, b1) with b0 <- |qc0; b1| <- |qc1; block|, or None if the
        parent chain is not yet locally available.  ``floor`` applies the
        snapshot barrier (see get_parent_block) to both hops."""
        b1 = await self.get_parent_block(block, floor)
        if b1 is None:
            return None
        b0 = await self.get_parent_block(b1, floor)
        if b0 is None:
            # Delivered blocks have stored ancestors (synchronizer.rs:142-146)
            # except across a snapshot cut (handled by the floor above);
            # reaching here means the store lost data.
            raise SerializationError(
                f"missing ancestor of delivered block {b1.digest()}"
            )
        return b0, b1

    def shutdown(self) -> None:
        if self._retry_task is not None:
            self._retry_task.cancel()
            self._retry_task = None
        for task in list(self._waiters):
            task.cancel()
        self._waiters.clear()
        self._by_parent.clear()
        self._children.clear()
        self.network.close()
