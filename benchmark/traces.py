"""Cross-node trace reconstruction from flight-recorder journals.

The per-node journals (hotstuff_tpu/telemetry/journal.py) each record one
node's view of the consensus lifecycle with that node's own clocks.  This
module merges a run's journals into one committee-wide timeline:

1. **Load** every ``*.jsonl`` ring segment under a journal directory,
   grouping records by the node named in each segment's meta line.
2. **Estimate per-node clock offsets** from matched send/recv pairs: a
   propose journaled at the leader and its recv.propose at a replica (or
   a vote.send and its recv.vote) give a one-way wall-clock delta per
   directed node pair.  The MEDIAN delta over a run approximates
   (typical network delay + clock offset) and is robust to the
   scheduling/GC outliers that poison a single extreme sample; with both
   directions measured the symmetric estimate
   ``offset = (d_ab - d_ba) / 2`` cancels the delay (NTP's classic
   assumption: symmetric paths).  Offsets are propagated from the
   best-connected reference node by BFS; nodes with NO matched pair
   (e.g. crashed before sending) degrade gracefully to offset 0 with a
   warning — never a crash.
3. **Reconstruct** every block's cross-node timeline — propose at the
   leader, receive/vote at each replica, QC formation, commit on every
   node — using corrected wall clocks for cross-node edges and raw
   monotonic clocks for same-node edges (immune to wall steps).
4. **Report**: a SUMMARY block with per-edge committee-wide gaps and
   straggler attribution (``summary()``), and a Chrome trace-event JSON
   openable in Perfetto / chrome://tracing (``export_chrome_trace()``):
   one track per node, one duration slice per block per node, one flow
   arrow per propose->recv edge, instant markers for timeouts.

Pure stdlib; no dependency on the node runtime (reads JSONL only) —
the only package import is the constant-leaf edge/stage registry
(``hotstuff_tpu/telemetry/taxonomy.py``), so every rendered edge name
comes from the same table the ``taxonomy-registry`` lint checks record
call sites against.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import Counter, defaultdict
from statistics import mean, median

from hotstuff_tpu.telemetry.taxonomy import (
    BYZ_PREFIX,
    CONTROL_EDGES,
    FAULT_PREFIX,
    HEALTH_PREFIX,
    INGEST_PREFIX,
    NET_PREFIX,
    RECONFIG_PREFIX,
    SPAN_ANNOTATION_STAGES,
)

#: a block counts as reconstructed when its commit can be attributed —
#: the propose anchor plus at least one receive edge were journaled
_SEG_RE = re.compile(r"^(?P<prefix>.+)-(?P<seq>\d{6})\.jsonl$")


# ---- loading ---------------------------------------------------------------


def load_journals(
    dir_path: str, stats: dict | None = None
) -> dict[str, list[dict]]:
    """node id -> that node's records, merged across ring segments and
    sorted by record sequence (falling back to monotonic time for
    journals predating the ``s`` field).  Torn lines (a crash mid-write)
    are skipped; the node id comes from each segment's meta line
    (filenames are sanitized and ambiguous).

    A crash-restarted node resumes its ring and can replay records whose
    sequence numbers were already persisted (a torn tail hides the true
    max seq) — duplicates are dropped by (node, seq), first occurrence
    wins.  When ``stats`` (a dict) is passed it is filled with the merge
    accounting: ``overlap`` (deduped records), ``loaded`` /``dropped``
    totals and per-node counts (``dropped`` comes from the ring's
    cumulative no-silent-caps counter in the meta lines)."""
    by_node: dict[str, list[dict]] = defaultdict(list)
    meta_drop: dict[str, int] = defaultdict(int)
    overlap = 0
    paths = sorted(glob.glob(os.path.join(dir_path, "*.jsonl")))
    for path in paths:
        node = None
        records = []
        drop = 0
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn line (crash mid-write)
                if rec.get("e") == "meta":
                    node = rec.get("n", node)
                    drop = max(drop, int(rec.get("drop", 0) or 0))
                    continue
                records.append(rec)
        if node is None:
            # segment lost its meta line: fall back to the filename prefix
            m = _SEG_RE.match(os.path.basename(path))
            node = m.group("prefix") if m else os.path.basename(path)
        by_node[node].extend(records)
        meta_drop[node] = max(meta_drop[node], drop)
    for node, records in by_node.items():
        seen: set[int] = set()
        deduped = []
        for r in records:
            s = r.get("s")
            if isinstance(s, int):
                if s in seen:
                    overlap += 1
                    continue
                seen.add(s)
            deduped.append(r)
        # segment files sort chronologically, so first occurrence wins;
        # order by seq when the journal carries it (restart-safe — the
        # monotonic clock resets across boots, seqs don't)
        if len(seen) == len(deduped):
            deduped.sort(key=lambda r: r.get("s", 0))
        else:
            deduped.sort(key=lambda r: r.get("m", 0))
        by_node[node] = deduped
    if stats is not None:
        loaded = {n: len(rs) for n, rs in by_node.items()}
        stats["overlap"] = overlap
        stats["loaded"] = sum(loaded.values())
        stats["dropped"] = sum(meta_drop.values())
        stats["by_node"] = {
            n: {"loaded": loaded[n], "dropped": meta_drop.get(n, 0)}
            for n in by_node
        }
    return dict(by_node)


def load_campaigns(dir_path: str) -> dict[str, dict]:
    """node id -> that node's persisted campaign ring (the
    ``<node>-campaign.json`` files the on-node recorder writes beside
    the journal segments; never matched by the ``*.jsonl`` glob above)."""
    from hotstuff_tpu.telemetry.health import CAMPAIGN_SUFFIX, CampaignRecorder

    out: dict[str, dict] = {}
    for path in sorted(
        glob.glob(os.path.join(dir_path, f"*{CAMPAIGN_SUFFIX}"))
    ):
        try:
            doc = CampaignRecorder.load(path)
        except (OSError, ValueError):
            continue  # torn write on a crashed node — merge the rest
        node = doc.get("node") or os.path.basename(path)[
            : -len(CAMPAIGN_SUFFIX)
        ]
        out[node] = doc
    return out


def merge_campaigns(dir_path: str, out_path: str) -> str | None:
    """Fold every node's campaign ring into one report artifact at
    ``out_path`` (the ``logs/campaign.json`` the traces task writes).
    Returns the path, or None when no campaign files exist.  The merged
    document keeps per-node sample series verbatim and adds a fleet
    header (nodes, per-node sample counts, common time range) so a
    campaign can be replotted without re-running anything."""
    campaigns = load_campaigns(dir_path)
    if not campaigns:
        return None
    spans = {}
    for node, doc in campaigns.items():
        ts = [s.get("t", 0.0) for s in doc.get("samples", ())]
        spans[node] = {
            "samples": len(ts),
            "from": min(ts) if ts else None,
            "to": max(ts) if ts else None,
        }
    merged = {
        "nodes": sorted(campaigns),
        "coverage": spans,
        "campaigns": campaigns,
    }
    parent = os.path.dirname(out_path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(merged, f, sort_keys=True)
    return out_path


# ---- clock-offset estimation ----------------------------------------------


def estimate_offsets(
    journals: dict[str, list[dict]],
    warnings: list | None = None,
) -> tuple[dict[str, int], str | None]:
    """(offsets, reference): per-node wall-clock offset in ns relative
    to the reference node (``corrected = w - offset[node]``).  Per
    directed pair the MEDIAN matched send/recv delta is used (robust to
    scheduling-spike outliers); nodes with no matched message pair to
    the connected component degrade gracefully to offset 0 (their
    cross-node edges are then only as good as NTP) with a line appended
    to ``warnings`` when a list is passed — never a crash."""
    # send-side indexes: who proposed each digest (and when), and when
    # each node sent its vote for each digest
    propose_at: dict[str, tuple[str, int]] = {}
    vote_sent: dict[tuple[str, str], int] = {}
    for node, records in journals.items():
        for r in records:
            e = r.get("e")
            d, w = r.get("d"), r.get("w")
            if d is None or w is None:
                continue
            if e == "propose" and d not in propose_at:
                propose_at[d] = (node, w)
            elif e == "vote.send":
                vote_sent.setdefault((d, node), w)

    # every observed one-way delta per directed pair (sender, receiver)
    deltas: dict[tuple[str, str], list[int]] = defaultdict(list)

    for node, records in journals.items():
        for r in records:
            e = r.get("e")
            d, w = r.get("d"), r.get("w")
            if d is None or w is None:
                continue
            if e == "recv.propose":
                src = propose_at.get(d)
                if src is not None and src[0] != node:
                    deltas[(src[0], node)].append(w - src[1])
            elif e == "recv.vote":
                peer = r.get("p", "")
                sent = vote_sent.get((d, peer))
                if sent is not None and peer != node:
                    deltas[(peer, node)].append(w - sent)

    # symmetric pairwise offsets where both directions were measured
    pair_offset: dict[tuple[str, str], float] = {}
    adjacency: dict[str, set[str]] = defaultdict(set)
    for (a, b), d_ab in deltas.items():
        d_ba = deltas.get((b, a))
        if d_ba is None or (a, b) in pair_offset:
            continue
        # clock(b) - clock(a), delay cancelled under symmetric medians
        off = (median(d_ab) - median(d_ba)) / 2.0
        pair_offset[(a, b)] = off
        pair_offset[(b, a)] = -off
        adjacency[a].add(b)
        adjacency[b].add(a)

    nodes = sorted(journals)
    if not nodes:
        return {}, None
    reference = max(nodes, key=lambda n: (len(adjacency.get(n, ())), n))
    offsets: dict[str, int] = {n: 0 for n in nodes}
    seen = {reference}
    frontier = [reference]
    while frontier:
        a = frontier.pop()
        for b in adjacency.get(a, ()):
            if b in seen:
                continue
            offsets[b] = offsets[a] + int(pair_offset[(a, b)])
            seen.add(b)
            frontier.append(b)
    if warnings is not None and len(nodes) > 1:
        for n in nodes:
            if n not in seen:
                warnings.append(
                    f"node {n}: no matched send/recv pair to reference"
                    f" {reference}; clock offset defaulted to 0"
                )
    return offsets, reference


# ---- reconstruction --------------------------------------------------------


class TraceSet:
    """A run's merged, clock-aligned committee timeline."""

    def __init__(
        self,
        journals: dict[str, list[dict]],
        merge_stats: dict | None = None,
    ):
        self.journals = journals
        self.nodes = sorted(journals)
        # merge accounting from load_journals (dedup overlap, ring-drop
        # counters) — the + CRITPATH journal-coverage line reads these
        self.merge_stats: dict = merge_stats or {}
        self.offset_warnings: list[str] = []
        self.offsets, self.reference = estimate_offsets(
            journals, self.offset_warnings
        )
        # digest -> timeline; every (m, w) pair below is (node-local
        # monotonic ns, offset-corrected wall ns)
        self.blocks: dict[str, dict] = {}
        # rounds that any node journaled a local timeout for, with the
        # corrected wall time of the first complaint
        self.timeouts: dict[int, tuple[str, int]] = {}
        # producer-channel edges (ROADMAP PR 2 follow-up): per-payload
        # wait from the leader's recv.producer to its payload.first, ms
        # on that node's monotonic clock
        self.payload_waits: list[float] = []
        # chaos-plane windows: (label, w_open_corr, w_close_corr|None),
        # taken from the node that journaled the most fault edges (every
        # node journals the same scenario schedule)
        self.fault_spans: list[tuple[str, int, int | None]] = []
        # adversary-plane windows, per attacking node (unlike fault
        # windows these are NOT committee-wide — only the Byzantine
        # nodes journal them): (node, label, w_open_corr, w_close|None)
        self.byz_spans: list[tuple[str, str, int, int | None]] = []
        # individual attack events: (w_corr, node, kind, round)
        self.byz_events: list[tuple[int, str, str, int]] = []
        # ingest-plane records (ISSUE 10): (w_corr, node, kind, value).
        # "shed" carries the shed payload count in the value, "credit"
        # the granted credit window (sampled every 64th decision).
        self.ingest_events: list[tuple[int, str, str, int]] = []
        # reconfiguration-plane records (ISSUE 14): (w_corr, node, step,
        # round) per journaled epoch-change step (submit/commit/
        # activate/retire/link)
        self.reconfig_events: list[tuple[int, str, str, int]] = []
        # network-plane flow samples (ISSUE 19): (w_corr, node,
        # direction, class, cumulative bytes).  The flow accountant
        # journals one net.tx/net.rx record per HOTSTUFF_NET_SAMPLE
        # charges; the class rides the peer field, the node's cumulative
        # direction bytes ride the "u" field.
        self.net_events: list[tuple[int, str, str, str, int]] = []
        # health-plane incident windows (ISSUE 13): (node, kind,
        # w_open_corr, w_close_corr|None).  Each node's in-process
        # monitor journals open/close per detector, phase in the peer
        # field (like adversary windows, these are per-node, not
        # committee-wide).
        self.health_spans: list[tuple[str, str, int, int | None]] = []
        # verify-pipeline profiler spans (ISSUE 4): node -> list of
        # (stage, w_end_corr, dur_ns).  A span record's timestamps mark
        # the span's END; its duration rides in the "u" field.
        self.verify_spans: dict[str, list[tuple[str, int, int]]] = {}
        # pipeline occupancy annotations (ISSUE 5): node -> list of
        # (w_corr, in-flight depth).  Value-encoded span records (the
        # "u" field carries the depth, not a duration) — kept apart so
        # the waterfall rows above never treat a depth as nanoseconds.
        self.occupancy_samples: dict[str, list[tuple[int, int]]] = {}
        self._reconstruct()

    @classmethod
    def load(cls, dir_path: str) -> "TraceSet":
        stats: dict = {}
        return cls(load_journals(dir_path, stats), merge_stats=stats)

    def journal_coverage(self) -> float:
        """Fraction of journaled records still in the ring at merge time
        (1.0 = nothing rotated away).  Attribution over a truncated ring
        is visibly partial, never silently wrong."""
        loaded = self.merge_stats.get("loaded", 0)
        dropped = self.merge_stats.get("dropped", 0)
        if not dropped:
            return 1.0
        return loaded / float(loaded + dropped)

    def _corr(self, node: str, w: int) -> int:
        return w - self.offsets.get(node, 0)

    def _block(self, digest: str, round_: int) -> dict:
        info = self.blocks.get(digest)
        if info is None:
            info = self.blocks[digest] = {
                "round": round_,
                "leader": None,
                "propose": None,  # (m, w_corr) at the leader
                "recv": {},  # node -> (m, w_corr), first arrival
                "vote_send": {},  # node -> (m, w_corr)
                "recv_vote": {},  # voter -> (recv node, m, w_corr), first
                "qc_form": None,  # (node, m, w_corr), QC assembled
                "qc": None,  # (node, m, w_corr), first high-QC adoption
                "commit": {},  # node -> (m, w_corr)
            }
        elif round_ and not info["round"]:
            info["round"] = round_
        return info

    def _reconstruct(self) -> None:
        fault_edges_best: list[tuple[int, str, str]] = []
        byz_edges: list[tuple[int, str, str, str]] = []  # (w, node, kind, label)
        health_edges: list[tuple[int, str, str, str]] = []  # (w, node, kind, phase)
        for node, records in self.journals.items():
            producer_seen: dict[str, int] = {}  # digest -> monotonic ns
            fault_edges: list[tuple[int, str, str]] = []  # (w_corr, kind, label)
            for r in records:
                e = r["e"]
                if e.startswith(BYZ_PREFIX):
                    # adversary-plane records must never reach _block
                    # (their "d" may be None)
                    w = self._corr(node, r["w"])
                    kind = e[len(BYZ_PREFIX):]
                    if kind in ("open", "close"):
                        byz_edges.append((w, node, kind, r.get("p", "")))
                    else:
                        self.byz_events.append(
                            (w, node, kind, int(r.get("r", 0)))
                        )
                    continue
                if e.startswith(HEALTH_PREFIX):
                    # health-plane records must never reach _block ("d"
                    # is None); open/close phase rides the peer field
                    health_edges.append(
                        (
                            self._corr(node, r["w"]),
                            node,
                            e[len(HEALTH_PREFIX):],
                            r.get("p", ""),
                        )
                    )
                    continue
                if e.startswith(INGEST_PREFIX):
                    # admission-plane records must never reach _block
                    # either ("d" is None); the shed count / credit
                    # window rides the "u" field
                    self.ingest_events.append(
                        (
                            self._corr(node, r["w"]),
                            node,
                            e[len(INGEST_PREFIX):],
                            int(r.get("u") or 0),
                        )
                    )
                    continue
                if e.startswith(RECONFIG_PREFIX):
                    # reconfiguration-plane records must never reach
                    # _block either ("d" is None)
                    self.reconfig_events.append(
                        (
                            self._corr(node, r["w"]),
                            node,
                            e[len(RECONFIG_PREFIX):],
                            int(r.get("r", 0) or 0),
                        )
                    )
                    continue
                if e.startswith(NET_PREFIX):
                    # network-plane samples must never reach _block
                    # either ("d" is None): class in the peer field,
                    # cumulative direction bytes in the "u" field
                    self.net_events.append(
                        (
                            self._corr(node, r["w"]),
                            node,
                            e[len(NET_PREFIX):],
                            r.get("p", ""),
                            int(r.get("u") or 0),
                        )
                    )
                    continue
                if e in CONTROL_EDGES:
                    continue
                if e in ("recv.producer", "recv.relay"):
                    # a relayed digest waits at the leader from the
                    # relay frame's receipt, as a client's from its own
                    producer_seen.setdefault(r["d"], r["m"])
                    continue
                if e == "payload.first":
                    got = producer_seen.get(r["d"])
                    if got is not None:
                        self.payload_waits.append((r["m"] - got) / 1e6)
                    continue
                if e == "span":
                    # profiler record: stage name in "p", duration in
                    # "u"; must not reach _block (d is empty)
                    dur = r.get("u")
                    if dur is not None:
                        if r["p"] in SPAN_ANNOTATION_STAGES:
                            # value annotation: "u" is in-flight depth
                            self.occupancy_samples.setdefault(
                                node, []
                            ).append((self._corr(node, r["w"]), int(dur)))
                        else:
                            self.verify_spans.setdefault(node, []).append(
                                (r["p"], self._corr(node, r["w"]), int(dur))
                            )
                    continue
                if e in (FAULT_PREFIX + "open", FAULT_PREFIX + "close"):
                    fault_edges.append(
                        (self._corr(node, r["w"]), e[len(FAULT_PREFIX):], r["p"])
                    )
                    continue
                if e == "timeout":
                    rnd = r["r"]
                    w = self._corr(node, r["w"])
                    if rnd not in self.timeouts or w < self.timeouts[rnd][1]:
                        self.timeouts[rnd] = (node, w)
                    continue
                stamp = (r["m"], self._corr(node, r["w"]))
                info = self._block(r["d"], r["r"])
                if e == "propose":
                    if info["propose"] is None:
                        info["leader"] = node
                        info["propose"] = stamp
                elif e == "recv.propose":
                    if node not in info["recv"]:
                        info["recv"][node] = stamp
                elif e == "vote.send":
                    info["vote_send"].setdefault(node, stamp)
                elif e == "recv.vote":
                    voter = r.get("p", "")
                    if voter and voter not in info["recv_vote"]:
                        info["recv_vote"][voter] = (node, r["m"], stamp[1])
                elif e == "qc.form":
                    if info["qc_form"] is None:
                        info["qc_form"] = (node, r["m"], stamp[1])
                elif e == "qc":
                    if info["qc"] is None:
                        info["qc"] = (node, r["m"], stamp[1])
                elif e == "commit":
                    info["commit"].setdefault(node, stamp)
            if len(fault_edges) > len(fault_edges_best):
                fault_edges_best = fault_edges
        # pair open/close edges per label, in time order
        open_at: dict[str, int] = {}
        for w, kind, label in sorted(fault_edges_best):
            if kind == "open":
                open_at.setdefault(label, w)
            elif label in open_at:
                self.fault_spans.append((label, open_at.pop(label), w))
        for label, w in open_at.items():  # never-closed windows
            self.fault_spans.append((label, w, None))
        self.fault_spans.sort(key=lambda s: s[1])
        # adversary windows pair per (node, label) — each Byzantine node
        # journals only its own schedule
        byz_open: dict[tuple[str, str], int] = {}
        for w, node, kind, label in sorted(byz_edges):
            key = (node, label)
            if kind == "open":
                byz_open.setdefault(key, w)
            elif key in byz_open:
                self.byz_spans.append((node, label, byz_open.pop(key), w))
        for (node, label), w in byz_open.items():
            self.byz_spans.append((node, label, w, None))
        self.byz_spans.sort(key=lambda s: s[2])
        self.byz_events.sort()
        self.ingest_events.sort()
        self.reconfig_events.sort()
        self.net_events.sort()
        # health incidents pair per (node, detector kind) — each node's
        # monitor journals only its own firings
        health_open: dict[tuple[str, str], int] = {}
        for w, node, kind, phase in sorted(health_edges):
            key = (node, kind)
            if phase == "open":
                health_open.setdefault(key, w)
            elif key in health_open:
                self.health_spans.append((node, kind, health_open.pop(key), w))
        for (node, kind), w in health_open.items():  # still-open incidents
            self.health_spans.append((node, kind, w, None))
        self.health_spans.sort(key=lambda s: s[2])

    # ---- derived views -----------------------------------------------------

    def committed(self) -> list[str]:
        """Digests with at least one commit record, oldest round first."""
        return sorted(
            (d for d, i in self.blocks.items() if i["commit"]),
            key=lambda d: self.blocks[d]["round"],
        )

    def reconstructed(self) -> list[str]:
        """Committed digests whose commit can be ATTRIBUTED: the propose
        anchor and at least one receive edge were journaled."""
        return [
            d
            for d in self.committed()
            if self.blocks[d]["propose"] is not None
            and self.blocks[d]["recv"]
        ]

    def coverage(self) -> float:
        committed = self.committed()
        if not committed:
            return 0.0
        return len(self.reconstructed()) / len(committed)

    def edge_gaps(self) -> dict:
        """Committee-wide per-edge statistics (ms floats) over the
        reconstructed blocks.  Cross-node edges use corrected wall
        clocks; same-node edges use that node's monotonic clock."""
        pr: list[float] = []  # propose -> replica recv (cross-node)
        spread: list[float] = []  # recv spread across replicas, per block
        rv: list[float] = []  # recv -> vote sent (same node, monotonic)
        pq: list[float] = []  # propose -> QC formed (cross-node)
        pc: list[float] = []  # propose -> commit (cross-node, all nodes)
        cspread: list[float] = []  # commit spread across nodes, per block
        recv_last: Counter = Counter()  # straggler: last to receive
        commit_last: Counter = Counter()  # straggler: last to commit
        for d in self.reconstructed():
            info = self.blocks[d]
            _, w0 = info["propose"]
            recvs = info["recv"]
            ws = [w for _, w in recvs.values()]
            pr.extend((w - w0) / 1e6 for w in ws)
            if len(ws) >= 2:
                spread.append((max(ws) - min(ws)) / 1e6)
                recv_last[max(recvs, key=lambda n: recvs[n][1])] += 1
            for node, (m_v, _) in info["vote_send"].items():
                got = recvs.get(node)
                if got is not None:
                    rv.append((m_v - got[0]) / 1e6)
            if info["qc"] is not None:
                pq.append((info["qc"][2] - w0) / 1e6)
            commits = info["commit"]
            cws = [w for _, w in commits.values()]
            pc.extend((w - w0) / 1e6 for w in cws)
            if len(cws) >= 2:
                cspread.append((max(cws) - min(cws)) / 1e6)
                commit_last[max(commits, key=lambda n: commits[n][1])] += 1
        return {
            "propose_to_recv": pr,
            "recv_spread": spread,
            "recv_to_vote": rv,
            "propose_to_qc": pq,
            "propose_to_commit": pc,
            "commit_spread": cspread,
            "recv_straggler": recv_last,
            "commit_straggler": commit_last,
        }

    # ---- reporting ---------------------------------------------------------

    def summary(self) -> str:
        """The ``+ CROSS-NODE TRACE`` SUMMARY block (appended to the
        bench SUMMARY by ``python -m benchmark local --journal``)."""
        committed = self.committed()
        if not self.nodes:
            return ""
        lines = [" + CROSS-NODE TRACE (flight recorder):\n"]
        lines.append(
            f" Nodes journaled: {len(self.nodes)};"
            f" committed blocks reconstructed:"
            f" {len(self.reconstructed())}/{len(committed)}"
            f" ({100.0 * self.coverage():.0f}%)\n"
        )
        if self.reference is not None and len(self.nodes) > 1:
            offs = ", ".join(
                f"{n} {self.offsets.get(n, 0) / 1e6:+.2f}"
                for n in self.nodes
                if n != self.reference
            )
            lines.append(
                f" Clock offsets vs {self.reference} (ms): {offs}\n"
            )
        for warning in self.offset_warnings:
            lines.append(f" WARN {warning}\n")
        overlap = self.merge_stats.get("overlap", 0)
        if overlap:
            lines.append(
                f" Journal merge: {overlap} replayed record(s) deduped"
                f" (crash-restart overlap)\n"
            )
        dropped = self.merge_stats.get("dropped", 0)
        if dropped:
            lines.append(
                f" Journal ring dropped {dropped} record(s)"
                f" (coverage {100.0 * self.journal_coverage():.0f}%)\n"
            )
        gaps = self.edge_gaps()

        def row(label: str, values: list[float], extra: str = "") -> None:
            if not values:
                return
            lines.append(
                f" {label + ':':<34} mean {mean(values):7.2f} ms"
                f"  max {max(values):7.2f} ms{extra}\n"
            )

        row("producer recv -> proposed", self.payload_waits)
        row("propose -> replica recv", gaps["propose_to_recv"])
        row("recv spread across committee", gaps["recv_spread"])
        row("recv -> vote sent (local)", gaps["recv_to_vote"])
        row("propose -> QC formed", gaps["propose_to_qc"])
        row("propose -> commit (all nodes)", gaps["propose_to_commit"])
        row("commit spread across committee", gaps["commit_spread"])
        for counter, label in (
            (gaps["recv_straggler"], "last to receive"),
            (gaps["commit_straggler"], "last to commit"),
        ):
            if counter:
                node, hits = counter.most_common(1)[0]
                total = sum(counter.values())
                lines.append(
                    f" Straggler ({label}): {node}"
                    f" ({100.0 * hits / total:.0f}% of {total} blocks)\n"
                )
        if self.timeouts:
            rounds = sorted(self.timeouts)
            shown = ", ".join(str(r) for r in rounds[:8])
            if len(rounds) > 8:
                shown += ", ..."
            lines.append(
                f" Timed-out rounds journaled: {len(rounds)} ({shown})\n"
            )
        if self.fault_spans:
            labels = Counter(label for label, _, _ in self.fault_spans)
            shown = ", ".join(
                f"{label} x{n}" if n > 1 else label
                for label, n in sorted(labels.items())
            )
            lines.append(
                f" Fault windows journaled: {len(self.fault_spans)}"
                f" ({shown})\n"
            )
        if self.byz_spans or self.byz_events:
            kinds = Counter(kind for _w, _n, kind, _r in self.byz_events)
            attackers = sorted(
                {s[0] for s in self.byz_spans}
                | {e[1] for e in self.byz_events}
            )
            shown = ", ".join(
                f"{kind} x{c}" if c > 1 else kind
                for kind, c in sorted(kinds.items())
            )
            lines.append(
                f" Adversary plane journaled: {len(self.byz_spans)}"
                f" window(s) on {', '.join(attackers)}"
                + (f"; attacks: {shown}" if shown else "")
                + "\n"
            )
        if self.ingest_events:
            shed = sum(
                v for _w, _n, k, v in self.ingest_events if k == "shed"
            )
            credits = [
                v for _w, _n, k, v in self.ingest_events if k == "credit"
            ]
            nodes = sorted({n for _w, n, _k, _v in self.ingest_events})
            lines.append(
                f" Ingest plane journaled: {len(self.ingest_events)}"
                f" edge(s) on {', '.join(nodes)};"
                f" payloads shed: {shed}"
                + (
                    f"; credit window mean {mean(credits):.0f}"
                    if credits
                    else ""
                )
                + "\n"
            )
        if self.net_events:
            nodes = sorted({n for _w, n, _d, _c, _v in self.net_events})
            peak_tx = max(
                (v for _w, _n, d, _c, v in self.net_events if d == "tx"),
                default=0,
            )
            peak_rx = max(
                (v for _w, _n, d, _c, v in self.net_events if d == "rx"),
                default=0,
            )
            lines.append(
                f" Network plane journaled: {len(self.net_events)}"
                f" flow sample(s) on {', '.join(nodes)};"
                f" peak per-node cumulative egress {peak_tx:,} B,"
                f" ingress {peak_rx:,} B\n"
            )
        if self.reconfig_events:
            steps = Counter(s for _w, _n, s, _r in self.reconfig_events)
            shown = ", ".join(
                f"{step} x{c}" if c > 1 else step
                for step, c in sorted(steps.items())
            )
            nodes = sorted({n for _w, n, _s, _r in self.reconfig_events})
            lines.append(
                f" Reconfiguration plane journaled:"
                f" {len(self.reconfig_events)} edge(s) on"
                f" {', '.join(nodes)} ({shown})\n"
            )
        if self.health_spans:
            kinds = Counter(k for _n, k, _o, _c in self.health_spans)
            shown = ", ".join(
                f"{kind} x{c}" if c > 1 else kind
                for kind, c in sorted(kinds.items())
            )
            still_open = sum(
                1 for _n, _k, _o, c in self.health_spans if c is None
            )
            lines.append(
                f" Health incidents journaled: {len(self.health_spans)}"
                f" ({shown})"
                + (f"; {still_open} never closed" if still_open else "")
                + "\n"
            )
        if self.verify_spans:
            total: Counter = Counter()
            count = 0
            for rows in self.verify_spans.values():
                count += len(rows)
                for stage, _w, dur in rows:
                    total[stage] += dur
            top = ", ".join(
                f"{stage} {ns / 1e6:.1f} ms"
                for stage, ns in total.most_common(3)
            )
            lines.append(
                f" Verify-pipeline spans journaled: {count}"
                f" (busiest stages: {top})\n"
            )
        return "".join(lines)

    # ---- Perfetto export ---------------------------------------------------

    def chrome_trace(self, critpath=None) -> dict:
        """Chrome trace-event JSON (the dict; see export_chrome_trace).
        One track (pid) per node; per block one duration slice per node
        that saw it (leader: propose->commit, replica: recv->commit)
        with a flow arrow per propose->recv edge; timeouts as instant
        markers.  ``critpath`` (an optional
        ``telemetry.critpath.CritPathReport``) adds a dedicated
        "critical path" track highlighting each commit's winning
        chain."""
        pid_of = {n: i for i, n in enumerate(self.nodes)}
        events: list[dict] = []
        for node, pid in pid_of.items():
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": 0,
                    "args": {"name": f"node {node}"},
                }
            )

        # everything is expressed in microseconds since the run's first
        # corrected wall timestamp
        anchors = [
            i["propose"][1] for i in self.blocks.values() if i["propose"]
        ]
        anchors.extend(w for _, w in self.timeouts.values())
        anchors.extend(w for _, w, _ in self.fault_spans)
        anchors.extend(w for _, _, w in self.fault_spans if w is not None)
        anchors.extend(w for _, _, w, _ in self.byz_spans)
        anchors.extend(w for _, _, _, w in self.byz_spans if w is not None)
        anchors.extend(w for w, _, _, _ in self.byz_events)
        anchors.extend(w for w, _, _, _ in self.ingest_events)
        anchors.extend(w for w, _, _, _ in self.reconfig_events)
        anchors.extend(w for w, _, _, _, _ in self.net_events)
        anchors.extend(w for _, _, w, _ in self.health_spans)
        anchors.extend(w for _, _, _, w in self.health_spans if w is not None)
        for rows in self.verify_spans.values():
            # a span's start = its end stamp minus its duration
            anchors.extend(w - dur for _, w, dur in rows)
        for samples in self.occupancy_samples.values():
            anchors.extend(w for w, _ in samples)
        if critpath is not None:
            for c in critpath.commits:
                anchors.extend(
                    s.w_start
                    for s in c.segments
                    if s.w_start is not None
                )
        if not anchors:
            return {"traceEvents": events, "displayTimeUnit": "ms"}
        base = min(anchors)
        horizon = max(anchors)

        def us(w_corr: int) -> float:
            return (w_corr - base) / 1e3

        for digest, info in sorted(
            self.blocks.items(), key=lambda kv: kv[1]["round"]
        ):
            if info["propose"] is None:
                continue
            rnd = info["round"]
            name = f"r{rnd} {digest[:8]}"
            args = {"round": rnd, "digest": digest}
            _, w0 = info["propose"]
            leader = info["leader"]
            ends = [w for _, w in info["commit"].values()]
            ends.append(w0)
            if info["qc"] is not None:
                ends.append(info["qc"][2])
            leader_end = info["commit"].get(leader)
            events.append(
                {
                    "name": name,
                    "cat": "block",
                    "ph": "X",
                    "pid": pid_of[leader],
                    "tid": 0,
                    "ts": us(w0),
                    "dur": max(
                        1.0,
                        us(leader_end[1] if leader_end else max(ends))
                        - us(w0),
                    ),
                    "args": {**args, "role": "leader"},
                }
            )
            for node, (_, w_recv) in info["recv"].items():
                end = info["commit"].get(node)
                vote = info["vote_send"].get(node)
                w_end = end[1] if end else (vote[1] if vote else w_recv)
                events.append(
                    {
                        "name": name,
                        "cat": "block",
                        "ph": "X",
                        "pid": pid_of[node],
                        "tid": 0,
                        "ts": us(w_recv),
                        "dur": max(1.0, us(w_end) - us(w_recv)),
                        "args": {**args, "role": "replica"},
                    }
                )
                # one flow arrow per propose->recv edge (flow ids must
                # be unique per arrow: digest alone would fan out)
                flow = {"cat": "flow", "name": f"propagate {name}"}
                events.append(
                    {
                        **flow,
                        "ph": "s",
                        "id": f"{digest}:{node}",
                        "pid": pid_of[leader],
                        "tid": 0,
                        "ts": us(w0),
                    }
                )
                events.append(
                    {
                        **flow,
                        "ph": "f",
                        "bp": "e",
                        "id": f"{digest}:{node}",
                        "pid": pid_of[node],
                        "tid": 0,
                        "ts": us(w_recv),
                    }
                )
        for rnd, (node, w) in sorted(self.timeouts.items()):
            events.append(
                {
                    "name": f"timeout r{rnd}",
                    "cat": "timeout",
                    "ph": "i",
                    "s": "p",
                    "pid": pid_of[node],
                    "tid": 0,
                    "ts": us(w),
                }
            )
        if self.fault_spans:
            # dedicated chaos track: partition/impairment windows as
            # duration slices spanning the whole committee timeline
            chaos_pid = len(self.nodes)
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": chaos_pid,
                    "tid": 0,
                    "args": {"name": "chaos plane"},
                }
            )
            for label, w_open, w_close in self.fault_spans:
                end = w_close if w_close is not None else horizon
                events.append(
                    {
                        "name": label,
                        "cat": "fault",
                        "ph": "X",
                        "pid": chaos_pid,
                        "tid": 0,
                        "ts": us(w_open),
                        "dur": max(1.0, us(end) - us(w_open)),
                        "args": {"label": label, "closed": w_close is not None},
                    }
                )
        if self.byz_spans or self.byz_events:
            # dedicated adversary track (one pid past the chaos plane):
            # policy windows as duration slices, one thread lane per
            # attacking node, individual attacks as instant markers
            byz_pid = len(self.nodes) + 1
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": byz_pid,
                    "tid": 0,
                    "args": {"name": "adversary plane"},
                }
            )
            attackers = sorted(
                {n for n, _l, _o, _c in self.byz_spans}
                | {n for _w, n, _k, _r in self.byz_events}
            )
            tid_of = {n: i for i, n in enumerate(attackers)}
            for n, tid in tid_of.items():
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": byz_pid,
                        "tid": tid,
                        "args": {"name": f"adversary {n}"},
                    }
                )
            for node, label, w_open, w_close in self.byz_spans:
                end = w_close if w_close is not None else horizon
                events.append(
                    {
                        "name": label,
                        "cat": "byz",
                        "ph": "X",
                        "pid": byz_pid,
                        "tid": tid_of[node],
                        "ts": us(w_open),
                        "dur": max(1.0, us(end) - us(w_open)),
                        "args": {
                            "label": label,
                            "node": node,
                            "closed": w_close is not None,
                        },
                    }
                )
            for w, node, kind, rnd in self.byz_events:
                events.append(
                    {
                        "name": f"byz {kind}" + (f" r{rnd}" if rnd else ""),
                        "cat": "byz",
                        "ph": "i",
                        "s": "t",
                        "pid": byz_pid,
                        "tid": tid_of[node],
                        "ts": us(w),
                        "args": {"kind": kind, "round": rnd, "node": node},
                    }
                )
        if self.ingest_events:
            # dedicated ingest-plane track (one pid past the adversary
            # plane): per-node lanes with admission sheds as instant
            # markers and the granted credit window as a counter series
            ingest_pid = len(self.nodes) + 2
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": ingest_pid,
                    "tid": 0,
                    "args": {"name": "ingest plane"},
                }
            )
            lanes = sorted({n for _w, n, _k, _v in self.ingest_events})
            tid_of = {n: i for i, n in enumerate(lanes)}
            for n, tid in tid_of.items():
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": ingest_pid,
                        "tid": tid,
                        "args": {"name": f"ingest {n}"},
                    }
                )
            for w, node, kind, value in self.ingest_events:
                if kind == "credit":
                    events.append(
                        {
                            "name": "ingest credit",
                            "cat": "ingest",
                            "ph": "C",
                            "pid": ingest_pid,
                            "tid": tid_of[node],
                            "ts": us(w),
                            "args": {"credit": value},
                        }
                    )
                else:
                    events.append(
                        {
                            "name": f"ingest {kind} x{value}",
                            "cat": "ingest",
                            "ph": "i",
                            "s": "t",
                            "pid": ingest_pid,
                            "tid": tid_of[node],
                            "ts": us(w),
                            "args": {
                                "kind": kind,
                                "count": value,
                                "node": node,
                            },
                        }
                    )
        if self.health_spans:
            # dedicated incidents track (one pid past the ingest plane):
            # per-node lanes, one duration slice per detector firing so
            # an incident reads directly against the consensus rounds and
            # fault windows it explains
            health_pid = len(self.nodes) + 3
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": health_pid,
                    "tid": 0,
                    "args": {"name": "incidents"},
                }
            )
            lanes = sorted({n for n, _k, _o, _c in self.health_spans})
            tid_of = {n: i for i, n in enumerate(lanes)}
            for n, tid in tid_of.items():
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": health_pid,
                        "tid": tid,
                        "args": {"name": f"health {n}"},
                    }
                )
            for node, kind, w_open, w_close in self.health_spans:
                end = w_close if w_close is not None else horizon
                events.append(
                    {
                        "name": kind,
                        "cat": "health",
                        "ph": "X",
                        "pid": health_pid,
                        "tid": tid_of[node],
                        "ts": us(w_open),
                        "dur": max(1.0, us(end) - us(w_open)),
                        "args": {
                            "kind": kind,
                            "node": node,
                            "closed": w_close is not None,
                        },
                    }
                )
        if self.reconfig_events:
            # dedicated reconfiguration track (one pid past the
            # incidents plane): per-node lanes with one instant marker
            # per journaled epoch-change step, so submit -> commit ->
            # activate -> retire reads directly against the rounds the
            # handoff spans
            reconfig_pid = len(self.nodes) + 4
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": reconfig_pid,
                    "tid": 0,
                    "args": {"name": "reconfiguration"},
                }
            )
            lanes = sorted({n for _w, n, _s, _r in self.reconfig_events})
            tid_of = {n: i for i, n in enumerate(lanes)}
            for n, tid in tid_of.items():
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": reconfig_pid,
                        "tid": tid,
                        "args": {"name": f"reconfig {n}"},
                    }
                )
            for w, node, step, rnd in self.reconfig_events:
                events.append(
                    {
                        "name": f"reconfig {step}"
                        + (f" r{rnd}" if rnd else ""),
                        "cat": "reconfig",
                        "ph": "i",
                        "s": "t",
                        "pid": reconfig_pid,
                        "tid": tid_of[node],
                        "ts": us(w),
                        "args": {"step": step, "round": rnd, "node": node},
                    }
                )
        if self.net_events:
            # dedicated network plane (one pid past the critical path):
            # one cumulative-bytes counter track per (node, direction) —
            # Perfetto renders the slope, i.e. per-node bandwidth — plus
            # one flow lane per message class with a marker per journaled
            # sample, so a propose burst reads directly against the
            # rounds and fault windows that caused it
            net_pid = len(self.nodes) + 6
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": net_pid,
                    "tid": 0,
                    "args": {"name": "network plane"},
                }
            )
            classes = sorted(
                {c for _w, _n, _d, c, _v in self.net_events if c}
            )
            tid_of = {c: i + 1 for i, c in enumerate(classes)}
            for c, tid in tid_of.items():
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": net_pid,
                        "tid": tid,
                        "args": {"name": f"flow {c}"},
                    }
                )
            for w, node, d, cls, v in self.net_events:
                events.append(
                    {
                        "name": f"net {d} {node}",
                        "cat": "net",
                        "ph": "C",
                        "pid": net_pid,
                        "tid": 0,
                        "ts": us(w),
                        "args": {"bytes": v},
                    }
                )
                if cls in tid_of:
                    events.append(
                        {
                            "name": f"{d} {cls}",
                            "cat": "net",
                            "ph": "i",
                            "s": "t",
                            "pid": net_pid,
                            "tid": tid_of[cls],
                            "ts": us(w),
                            "args": {
                                "node": node,
                                "dir": d,
                                "class": cls,
                                "cum_bytes": v,
                            },
                        }
                    )
        for node, rows in sorted(self.verify_spans.items()):
            # verify-pipeline profiler track (ISSUE 4): one thread lane
            # under the journaling node's process, so the dispatch
            # waterfall lines up against the same node's consensus
            # rounds on the shared timeline
            pid = pid_of.get(node)
            if pid is None:
                continue
            events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": 1,
                    "args": {"name": "verify pipeline"},
                }
            )
            for stage, w_end, dur in rows:
                events.append(
                    {
                        "name": stage,
                        "cat": "verify",
                        "ph": "X",
                        "pid": pid,
                        "tid": 1,
                        "ts": us(w_end - dur),
                        "dur": max(0.1, dur / 1e3),
                        "args": {"stage": stage, "dur_ms": dur / 1e6},
                    }
                )
        for node, samples in sorted(self.occupancy_samples.items()):
            # dispatch-pipeline occupancy (ISSUE 5): a counter series on
            # the same node process as the verify-pipeline lane, so
            # in-flight depth reads directly against the waterfall
            pid = pid_of.get(node)
            if pid is None:
                continue
            for w, depth in samples:
                events.append(
                    {
                        "name": "verify inflight",
                        "cat": "verify",
                        "ph": "C",
                        "pid": pid,
                        "tid": 1,
                        "ts": us(w),
                        "args": {"inflight": depth},
                    }
                )
        if critpath is not None and critpath.commits:
            # dedicated critical-path track (one pid past the
            # reconfiguration plane): per commit, the winning causal
            # chain as contiguous stage slices — the one lane that says
            # where THIS block's wall-clock went
            crit_pid = len(self.nodes) + 5
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": crit_pid,
                    "tid": 0,
                    "args": {"name": "critical path"},
                }
            )
            # pipelined rounds overlap in time: cycle a few lanes so
            # consecutive chains don't stack into one malformed nest
            for lane in range(4):
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": crit_pid,
                        "tid": lane,
                        "args": {"name": f"chain lane {lane}"},
                    }
                )
            for c in critpath.commits:
                for seg in c.segments:
                    if seg.w_start is None or seg.w_end is None:
                        continue
                    events.append(
                        {
                            "name": seg.stage,
                            "cat": "critpath",
                            "ph": "X",
                            "pid": crit_pid,
                            "tid": c.round % 4,
                            "ts": us(seg.w_start),
                            "dur": max(1.0, us(seg.w_end) - us(seg.w_start)),
                            "args": {
                                "stage": seg.stage,
                                "detail": seg.detail,
                                "round": c.round,
                                "digest": c.digest,
                                "ms": round(seg.ms, 3),
                            },
                        }
                    )
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str, critpath=None) -> str:
        """Write the Chrome trace-event JSON; open in https://ui.perfetto.dev
        (or chrome://tracing).  Returns ``path``."""
        doc = self.chrome_trace(critpath=critpath)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


__all__ = [
    "load_journals",
    "load_campaigns",
    "merge_campaigns",
    "estimate_offsets",
    "TraceSet",
]
