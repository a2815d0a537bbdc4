"""Central span-stage and journal-edge taxonomy (ISSUE 12).

Every span stage name (``telemetry/spans.py``) and every journal edge
name (``telemetry/journal.py`` records) is registered HERE, and here
only.  ``benchmark/traces.py`` renders from these same tables, so an
edge that isn't registered is a **lint error**
(``hotstuff_tpu/analysis`` rule ``taxonomy-registry``) instead of a
silently-empty Perfetto track.

Adding an edge or stage is a two-line change: record it at the call
site, register it here (with the rendering group it belongs to).  The
lint rule cross-checks both directions: call sites must use registered
names, and ``traces.py`` must route every registered group.

This module is a pure-constant leaf: stdlib only, no imports, safe for
``benchmark/traces.py`` (which otherwise has no node-runtime
dependency) and for the analysis plane running in a bare CI venv.
"""

# ---- verify-pipeline span stages (telemetry/spans.py) ----------------------

#: leaf stages, in pipeline order — the canonical waterfall rows; spans
#: with other names (parents, ad-hoc) are recorded but never summed
SPAN_LEAF_STAGES: tuple = (
    "coalesce.wait",
    "native.pack",
    "route.decide",
    "pipeline.wait",
    "stage.pack",
    "stage.slot_wait",
    "flatten",
    "prepare",
    "dispatch",
    "device.execute",
    "mesh.psum",
    "readback",
    "host.verify",
    "host.pairing",
    "verdict.fanout",
)

#: frame spans: overlap the leaves, excluded from waterfall sums
SPAN_PARENT_STAGES: tuple = (
    "e2e",
    "dispatch.wall",
    "dispatch.chunk",  # one backend call of a wave: hands down chunk, lanes
    "agg.verify",
    "scheme.route",
)

#: value annotations: span records whose duration field encodes a VALUE
#: (e.g. in-flight wave depth), excluded from waterfall sums and
#: rendered as counter series
SPAN_ANNOTATION_STAGES: tuple = ("pipeline.occupancy",)

#: BLS-aggregation detail stages (crypto/bls/service.py, tpu/bls.py):
#: sub-phases of the ``agg.verify`` parent frame — recorded and
#: histogrammed, never waterfall rows.  Surfaced as unregistered drift
#: by the taxonomy-registry lint the day it landed (ISSUE 12).
#: The ``bls.*`` stages are the loop's own BLS work around them:
#: ``chipbench/readers/bls.py`` reads their self time on the loop thread
SPAN_AGG_STAGES: tuple = (
    "agg.gather",
    "agg.keysum",
    "agg.pairing",
    "agg.accumulate",  # one vote's device add (tpu/bls.py)
    "agg.snapshot",  # the running sum's fence and readback at quorum
    "bls.sign",  # BlsSigningService.sign_sync: hash to G1, scalar multiply
    "bls.decode",  # a vote signature decompressed for the running sum
)

#: host stages outside the verify waterfall, one prefix a layer of
#: PERF.md section 3: each wraps one SYNCHRONOUS segment on the
#: event-loop thread (no ``await`` inside: lint rule no-await-in-span)
#: and lands in the profiler's trace, where
#: ``chipbench/hostspans.py`` sums a layer's self time by prefix.
#: Never waterfall rows.
SPAN_HOST_STAGES: tuple = (
    # consensus: consensus/core.py, synchronizer.py, crypto/service.py
    "core.claims",  # burst claim collection / verdict memoization
    "core.proposal",  # leader check, Block.verify, QC processing
    "core.ancestors",  # parent lookup among the kept blocks; a miss's decode
    "core.persist",  # ConsensusState / block serialization
    "core.vote.make",  # safety rules, Vote, its frame
    "core.sign",  # the signing call (votes, blocks, timeouts)
    "core.vote",  # aggregator.add_vote, QC assembly, round advance
    "core.commit",  # per committed block: log line, execution layer
    # consensus/proposer.py
    "proposer.make",  # payload take, Block, log line, propose frame
    "proposer.cleanup",  # committed digests pruned, orphans re-buffered
    # network: consensus/consensus.py handler, network/*sender.py
    "net.decode",  # frame -> message on receive
    "net.send",  # enqueue on a peer connection (send / broadcast)
    "net.write",  # frame -> socket
    "net.ack",  # a reliable sender's ACK resolved
    # store: store/__init__.py, store/state.py via the core
    "store.read",
    "store.write",
    "store.apply",  # a committed block through the execution layer
    # ingest: the producer path
    "ingest.admit",  # one producer frame: content check, admission
    "ingest.buffer",  # digests into the proposer's buffer
    "ingest.relay",  # one relay frame: built and sent, or received
    # verify service, on the loop thread
    "verify.submit",  # a core's claims join the pending wave
    "verify.collect",  # the wave's dedup and collection
    "verify.spawn",  # hand-off to a slot thread
    "verify.deliver",  # verdicts to every waiter's future
    # the loop's own waiting: select() with a timeout (node/main.py)
    "loop.idle",
)

#: the event loop's callbacks, one ``cb`` annotation a handle it runs
#: while a profiler session is active (``telemetry/spans.py``
#: trace_callbacks, the loop of ``node/main.py``), its ``kind`` and
#: ``name=<qualname>`` set inside it; ``chipbench/loopcalls.py`` reads
#: it under these names.  No layer's: the layer spans nest inside them,
#: and ``chipbench/hostspans.py`` drops them (no ``cb`` prefix there).
SPAN_LOOP_CALLBACKS: tuple = (
    "cb.task",  # a Task's step or wake-up: name is the coroutine's
    "cb.io",  # a reader / writer callback the selector reported ready
    "cb.timer",  # a TimerHandle: call_later / call_at, asyncio.sleep
    "cb.call",  # any other ready handle: done-callbacks, threadsafe calls
)

#: every registered span stage name (what ``span("...")`` /
#: ``rec.add("...")`` call sites are checked against)
SPAN_STAGES: frozenset = frozenset(
    SPAN_LEAF_STAGES
    + SPAN_PARENT_STAGES
    + SPAN_ANNOTATION_STAGES
    + SPAN_AGG_STAGES
    + SPAN_HOST_STAGES
    + SPAN_LOOP_CALLBACKS
)

# ---- journal edges (telemetry/journal.py records) --------------------------

#: block-lifecycle edges: ``traces.py`` folds these into per-block
#: cross-node timelines (propose anchor, receive fan-out, vote, QC,
#: commit)
BLOCK_EDGES: tuple = (
    "propose",
    "recv.propose",
    "vote.send",
    "recv.vote",
    "qc.form",
    "qc",
    "commit",
)

#: control-plane edges: journaled for the SUMMARY/debugging but
#: excluded from per-block reconstruction (several carry no digest)
CONTROL_EDGES: tuple = (
    "tc",
    "round.enter",
    "recv.timeout",
    "recv.tc",
    "sync.req",
    "sync.reply",
    "sync.done",
    "sync.expire",
    "sync.serve",
    "sync.manifest",
    "sync.chunk",
    "sync.adopt",
    "recv.sync_req",
    "recv.state_req",
    "state.apply",
    "recv.reconfig",
)

#: producer-channel edges: leader-side payload wait attribution
PAYLOAD_EDGES: tuple = ("recv.producer", "recv.relay", "payload.first")

#: admission-plane edges: value records (shed count / credit window in
#: the ``u`` field), rendered as the ingest-plane track
INGEST_EDGES: tuple = ("ingest.shed", "ingest.credit")

#: zero-copy ingest metrics (ISSUE 20): registry counter names for
#: waves the verify service adopted straight from a native staging
#: arena vs. vote-overlapping waves that had to fall back to the
#: Python flatten path (disjoint non-vote waves count as neither).
#: The hit rate zc/(zc+fb) is surfaced on the verify stats line
#: (``zc=``/``fb=``) and asserted >=0.9 by scripts/ingest_check.py.
INGEST_COUNTERS: tuple = ("ingest_zero_copy_waves", "ingest_fallback_waves")

#: standalone edges: local timeout complaints, the profiler fan-out
#: record (stage in ``p``, duration in ``u``), and each ring segment's
#: identity line
MISC_EDGES: tuple = ("timeout", "span", "meta")

#: dynamic edge families: the chaos plane journals ``fault.<kind>``,
#: the adversary plane ``byz.<kind>``, the health plane
#: ``health.<kind>`` (telemetry/health.py detector incidents, open/close
#: in the peer field; the fleet-level ``health.epoch_skew`` rides the
#: same family) with scenario-/detector-defined kinds, and the live
#: reconfiguration plane ``reconfig.<step>`` (submit/commit/activate/
#: retire/link — consensus/core.py, reconfig.py); an f-string edge is
#: lint-legal iff its constant prefix is listed here
FAULT_PREFIX = "fault."
BYZ_PREFIX = "byz."
INGEST_PREFIX = "ingest."
HEALTH_PREFIX = "health."
RECONFIG_PREFIX = "reconfig."
NET_PREFIX = "net."
JOURNAL_EDGE_PREFIXES: tuple = (
    FAULT_PREFIX,
    BYZ_PREFIX,
    HEALTH_PREFIX,
    RECONFIG_PREFIX,
    NET_PREFIX,
)

# ---- wire-level flow classes (telemetry/flows.py) --------------------------

#: every message class the flow accounting plane charges a frame to —
#: derived from the wire-tag taxonomy (consensus/wire.py first byte;
#: ``telemetry/flows.py`` owns the byte->class map, and
#: ``tests/test_flows.py`` cross-checks it against the live wire
#: constants so tag drift is a test failure, not a silently-mislabelled
#: flow).  ``qc-compact`` wire cost rides inside ``propose`` frames and
#: is reported from the aggregator telemetry next to these classes.
FLOW_CLASSES: tuple = (
    "propose",
    "vote",
    "timeout",
    "tc",
    "sync-req",
    "producer-v1",
    "producer-v2",
    "ingest-ack",
    "state-sync",
    "reconfig",
    "relay",
    "ack",
    "other",
)

#: flow directions: every accounted frame is charged to exactly one
#: ``(peer, direction, class)`` flow at its send and its receive site
FLOW_DIRECTIONS: tuple = ("tx", "rx")

#: every registered static journal edge name (what ``journal.record``
#: call sites are checked against)
JOURNAL_EDGES: frozenset = frozenset(
    BLOCK_EDGES + CONTROL_EDGES + PAYLOAD_EDGES + INGEST_EDGES + MISC_EDGES
)


# ---- commit critical-path stages (telemetry/critpath.py) -------------------

#: critical-path stage taxonomy: every stage the commit critical-path
#: engine (``telemetry/critpath.py``) attributes latency to.  Two-round
#: chained-HotStuff commit means the per-round stages (net.propose,
#: vote.local, net.vote, agg.form) each appear once per chained round
#: and sum into one bucket.  ``unattributed`` is the residual between
#: the measured propose->commit wall and the sum of reconstructed
#: segments — rendered, never hidden.
CRITPATH_STAGES: tuple = (
    "ingest.wait",  # leader payload wait: producer recv -> propose
    "net.propose",  # propose broadcast -> quorum-th replica receive
    "vote.local",  # replica receive -> vote send (verify + sign)
    "net.vote",  # vote send -> receive at the aggregating node
    "agg.form",  # quorum-th vote receive -> QC assembled
    "lead.handoff",  # QC formed -> next-round proposal broadcast
    "commit.exec",  # chained QC formed -> commit observed at the node
    "unattributed",  # residual: measured total minus reconstructed sum
)

#: regime classification: which stage buckets vote for which regime —
#: the argmax group over attributed milliseconds names the run
CRITPATH_REGIMES: dict = {
    "ingest-bound": ("ingest.wait",),
    "network-bound": ("net.propose", "net.vote", "commit.exec"),
    "verify-bound": ("vote.local",),
    "aggregation-bound": ("agg.form", "lead.handoff"),
}


def is_registered_edge(name: str) -> bool:
    """Is ``name`` a registered journal edge (static or dynamic)?"""
    return name in JOURNAL_EDGES or name.startswith(JOURNAL_EDGE_PREFIXES)


def is_registered_stage(name: str) -> bool:
    """Is ``name`` a registered verify-pipeline span stage?"""
    return name in SPAN_STAGES


__all__ = [
    "SPAN_LEAF_STAGES",
    "SPAN_PARENT_STAGES",
    "SPAN_ANNOTATION_STAGES",
    "SPAN_AGG_STAGES",
    "SPAN_HOST_STAGES",
    "SPAN_LOOP_CALLBACKS",
    "SPAN_STAGES",
    "BLOCK_EDGES",
    "CONTROL_EDGES",
    "PAYLOAD_EDGES",
    "INGEST_EDGES",
    "INGEST_COUNTERS",
    "MISC_EDGES",
    "FAULT_PREFIX",
    "BYZ_PREFIX",
    "INGEST_PREFIX",
    "HEALTH_PREFIX",
    "RECONFIG_PREFIX",
    "NET_PREFIX",
    "FLOW_CLASSES",
    "FLOW_DIRECTIONS",
    "JOURNAL_EDGE_PREFIXES",
    "JOURNAL_EDGES",
    "CRITPATH_STAGES",
    "CRITPATH_REGIMES",
    "is_registered_edge",
    "is_registered_stage",
]
