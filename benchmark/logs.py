"""Log parsing — the measurement methodology.

Parity target: reference ``benchmark/benchmark/logs.py:15-225``, with the
log-schema contract CORRECTED for this framework (the reference's regexes
are stale against its own fork — SURVEY.md §2.6). The schema, defined
here and emitted by the framework:

node logs (hotstuff_tpu.consensus.*):
  ``Created block <round> (payloads <d1>,<d2>,...) -> <block_digest>`` (proposer)
  ``Committed block <round> -> <block_digest>``                    (core)
  ``Timeout reached for round <round>``                            (core)
  ``Timeout delay set to <ms> ms``                                 (config echo)
client logs (hotstuff_tpu.node.client):
  ``Transactions rate: <rate> tx/s``
  ``Sending sample payload <digest>``
  ``Transaction rate too high for this client``

Metric definitions (mirroring reference logs.py:147-180):
- consensus TPS: UNIQUE committed payload digests / (last commit -
  first proposal), proposals/commits merged across all node logs taking
  the earliest observation per block (deduplication means a payload
  re-proposed after a view change is counted once);
- consensus latency: proposal->commit per block digest;
- end-to-end TPS: same count over (client start - last commit);
- end-to-end latency: sample payload client-send -> commit of the block
  that contains that payload (payload->block map from Created lines).
"""

from __future__ import annotations

import glob
import os
import re
from collections import Counter
from datetime import datetime
from statistics import mean

from .utils import BenchError

_TS = r"(\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\.\d{3})Z"

RE_CREATED = re.compile(
    _TS + r".*Created block (\d+) \(payloads (\S*)\) -> (\S+)"
)
RE_COMMITTED = re.compile(_TS + r".*Committed block (\d+) -> (\S+)")
# replicated-execution state root per applied commit (core contract:
# ``State root <version> -> <root> (round <round>)``) — the basis of the
# cross-node state-root agreement invariant (benchmark/invariants.py)
RE_STATE_ROOT = re.compile(
    _TS + r".*State root (\d+) -> (\S+) \(round (\d+)\)"
)
# live-reconfiguration boundary crossing (core contract:
# ``Epoch <epoch> activated at round <round>``) — feeds the SUMMARY's
# epoch-transition lines here and the epoch-agreement invariant
# (benchmark/invariants.py)
RE_EPOCH = re.compile(_TS + r".*Epoch (\d+) activated at round (\d+)")
RE_TIMEOUT = re.compile(_TS + r".*Timeout reached for round (\d+)")
RE_TIMEOUT_DELAY = re.compile(r"Timeout delay set to (\d+) ms")
RE_CLIENT_RATE = re.compile(_TS + r".*Transactions rate: (\d+) tx/s")
RE_CLIENT_SIZE = re.compile(r"Transactions size: (\d+) B")
RE_SAMPLE = re.compile(_TS + r".*Sending sample payload (\S+)")
RE_RATE_HIGH = re.compile(r"rate too high")
# cumulative per-service routing counters (async_service._log_stats);
# the [tag] identifies the service instance so the LAST line per tag is
# its total
RE_VERIFY_STATS = re.compile(
    r"Verify service stats \[(\S+)\]: dispatches=(\d+) device=(\d+) "
    r"(?:cpu=(\d+) probe=(\d+) )?"
    r"device_sigs=(\d+) cpu_sigs=(\d+) deadline_misses=(\d+) "
    r"(?:waits=(\d+) depth=(\d+) )?"
    r"(?:mesh=(\d+) )?"
    r"(?:agg=(\d+) agg_sigs=(\d+) )?"
    r"ewma_ms=([\d.]+)"
    r"(?: zc=(\d+) fb=(\d+))?"
)
# periodic per-node telemetry snapshot (telemetry/exporter.py) — a
# cumulative JSON document; keep the LAST line per node log
RE_TELEMETRY = re.compile(r"Telemetry snapshot: (\{.*\})")
# health-plane incident transitions (telemetry/health.py HealthMonitor):
# one JSON document per detector open/close, timestamped so the SLO
# burn-rate can be integrated over the run
RE_HEALTH = re.compile(_TS + r".*Health incident: (\{.*\})")
RE_HEALTH_ON = re.compile(r"Health monitor running")


def _ts(s: str) -> float:
    return datetime.strptime(s, "%Y-%m-%dT%H:%M:%S.%f").timestamp()


class LogParser:
    def __init__(self, node_logs: list[str], client_logs: list[str]):
        """Args are the log *contents* (one string per file)."""
        if not node_logs:
            raise BenchError("No node logs to parse")
        self.num_node_logs = len(node_logs)

        # merged earliest observation per block digest
        self.proposals: dict[str, float] = {}
        self.commits: dict[str, float] = {}
        self.payload_to_block: dict[str, str] = {}
        self.block_payloads: dict[str, tuple[str, ...]] = {}
        self.block_round: dict[str, int] = {}
        self.timeouts = 0
        self.timeout_delay: int | None = None
        # live-reconfiguration boundary crossings: epoch -> the set of
        # activation rounds nodes reported (honest runs report ONE)
        self.epoch_activations: dict[int, set[int]] = {}

        for content in node_logs:
            for ts, rnd, payloads, block in RE_CREATED.findall(content):
                t = _ts(ts)
                if block not in self.proposals or t < self.proposals[block]:
                    self.proposals[block] = t
                plist = tuple(p for p in payloads.split(",") if p)
                self.block_payloads[block] = plist
                for p in plist:
                    self.payload_to_block[p] = block
                self.block_round[block] = int(rnd)
            for ts, rnd, block in RE_COMMITTED.findall(content):
                t = _ts(ts)
                if block not in self.commits or t < self.commits[block]:
                    self.commits[block] = t
                self.block_round.setdefault(block, int(rnd))
            self.timeouts += len(RE_TIMEOUT.findall(content))
            for _ts_, epoch, rnd in RE_EPOCH.findall(content):
                self.epoch_activations.setdefault(int(epoch), set()).add(
                    int(rnd)
                )
            m = RE_TIMEOUT_DELAY.search(content)
            if m:
                self.timeout_delay = int(m.group(1))

        # verify-service routing split: counters are cumulative per
        # service instance, so keep each tag's LAST line and sum tags.
        # This is the device-routing PROOF for tpu-verifier runs
        # (VERDICT r5 item 1): device_sigs vs cpu_sigs says where
        # claims were actually served.
        # keyed by (log file, tag): tags embed pid+serial, which is
        # unique within a host but can collide across hosts in a remote
        # sweep — the log file disambiguates
        # pre-pipeline logs omit the cpu=/probe=/waits=/depth= fields
        # (optional regex groups come back as '') — treat them as 0
        per_tag: dict[tuple, tuple] = {}
        for log_idx, content in enumerate(node_logs):
            for (
                tag, disp, dev, cpu, probe, dsig, csig, miss, waits,
                depth, mesh, agg, agg_sigs, ewma, zc, fb,
            ) in RE_VERIFY_STATS.findall(content):
                per_tag[(log_idx, tag)] = (
                    int(disp), int(dsig), int(csig), int(miss),
                    float(ewma), int(dev), int(cpu or 0), int(probe or 0),
                    int(waits or 0), int(depth or 1), int(mesh or 0),
                    int(agg or 0), int(agg_sigs or 0),
                    int(zc or 0), int(fb or 0),
                )
        self.device_sigs = sum(v[1] for v in per_tag.values())
        self.cpu_route_sigs = sum(v[2] for v in per_tag.values())
        self.deadline_misses = sum(v[3] for v in per_tag.values())
        self.verify_ewma_ms = (
            max(v[4] for v in per_tag.values()) if per_tag else None
        )
        # dispatch-wave routing split (ISSUE 5): waves by final route,
        # plus depth-cap queue events and the configured pipeline depth.
        # mesh= (ISSUE 7) is a SUBSET of device= (sharded-mesh backend
        # dispatches), so "device" here reports single-device waves only
        # and device+mesh reproduces the raw device= counter.
        _mesh = sum(v[10] for v in per_tag.values())
        self.route_waves = {
            "device": sum(v[5] for v in per_tag.values()) - _mesh,
            "mesh": _mesh,
            "cpu": sum(v[6] for v in per_tag.values()),
            "probe": sum(v[7] for v in per_tag.values()),
        }
        self.pipeline_waits = sum(v[8] for v in per_tag.values())
        self.pipeline_depth = (
            max(v[9] for v in per_tag.values()) if per_tag else None
        )
        # aggregate-certificate route (ISSUE 9): "agg" claims served by
        # ONE pairing over the bitmap-selected key sum instead of a
        # per-signature batch; agg_sigs counts the votes those compact
        # certificates stood in for
        self.agg_claims = sum(v[11] for v in per_tag.values())
        self.agg_claim_sigs = sum(v[12] for v in per_tag.values())
        # zero-copy ingest split (ISSUE 20): waves adopted straight
        # from a native staging arena vs. vote-overlapping waves that
        # fell back to the Python flatten path; pre-ingest logs omit
        # the zc=/fb= suffix and read as 0/0 (hit rate renders as '-')
        self.zero_copy_waves = sum(v[13] for v in per_tag.values())
        self.ingest_fallback_waves = sum(v[14] for v in per_tag.values())

        # telemetry snapshots (cumulative): last document per node log
        import json as _json

        self.telemetry_docs: list[dict] = []
        for content in node_logs:
            matches = RE_TELEMETRY.findall(content)
            if not matches:
                continue
            try:
                self.telemetry_docs.append(_json.loads(matches[-1]))
            except ValueError:
                pass  # truncated log line mid-write

        # health-plane incidents (ISSUE 13): every detector open/close
        # transition with its wall time, plus how many nodes ran the
        # in-process monitor (so a quiet run still renders the block —
        # "detectors on, nothing fired" is the healthy-run proof)
        self.health_nodes = 0
        self.health_events: list[tuple[float, dict]] = []
        for content in node_logs:
            if RE_HEALTH_ON.search(content):
                self.health_nodes += 1
            for ts, blob in RE_HEALTH.findall(content):
                try:
                    doc = _json.loads(blob)
                except ValueError:
                    continue  # truncated log line mid-write
                self.health_events.append((_ts(ts), doc))
        self.health_events.sort(key=lambda e: e[0])

        # wire-level flow accounting (ISSUE 19): the flows section of
        # each node's last snapshot — per-(peer, dir, class) byte
        # ledgers plus the per-class amplification factors.  A doc with
        # {"enabled": False} means the node ran with HOTSTUFF_NET=0:
        # the block renders n/a rather than vanishing, so "accounting
        # off" is never mistaken for "no traffic".
        self.flow_docs: list[dict] = [
            d["flows"]
            for d in self.telemetry_docs
            if isinstance(d.get("flows"), dict)
        ]

        # compact-certificate telemetry (ISSUE 9): the aggregator section
        # records the last emitted QC's wire size (compact = agg sig +
        # signer bitmap, vote-list = n x full votes) and how many
        # certificates took the compact form
        _agg_sections = [
            d.get("aggregator", {}) for d in self.telemetry_docs
        ]
        self.qc_wire_bytes = max(
            (s.get("qc_wire_bytes", 0) for s in _agg_sections), default=0
        ) or None
        self.compact_qcs = sum(
            s.get("compact_qcs_total", 0) for s in _agg_sections
        )
        self.compact_tcs = sum(
            s.get("compact_tcs_total", 0) for s in _agg_sections
        )

        # only blocks whose proposal we saw count toward latency
        self.commits = {
            b: t for b, t in self.commits.items() if b in self.proposals
        }

        self.client_start: float | None = None
        self.input_rate: int | None = None
        self.tx_size: int = 0  # payload body bytes (0 = digest-only)
        self.samples: dict[str, float] = {}  # payload -> send time
        self.rate_warnings = 0
        for content in client_logs:
            m = RE_CLIENT_RATE.search(content)
            if m:
                self.client_start = _ts(m.group(1))
                self.input_rate = int(m.group(2))
            m = RE_CLIENT_SIZE.search(content)
            if m:
                self.tx_size = int(m.group(1))
            for ts, payload in RE_SAMPLE.findall(content):
                self.samples[payload] = _ts(ts)
            self.rate_warnings += len(RE_RATE_HIGH.findall(content))

    @classmethod
    def process(cls, logs_dir: str) -> "LogParser":
        node_logs, client_logs = [], []
        for path in sorted(glob.glob(os.path.join(logs_dir, "node-*.log"))):
            with open(path) as f:
                node_logs.append(f.read())
        for path in sorted(glob.glob(os.path.join(logs_dir, "client*.log"))):
            with open(path) as f:
                client_logs.append(f.read())
        return cls(node_logs, client_logs)

    # ---- metrics (reference logs.py:147-180) -------------------------------

    def committed_payloads(self) -> int:
        """UNIQUE payload digests inside committed blocks (a payload
        re-proposed after a view change is counted once)."""
        unique: set[str] = set()
        for block in self.commits:
            unique.update(self.block_payloads.get(block, ()))
        return len(unique)

    def consensus_throughput(self) -> tuple[float, float]:
        """(unique committed payloads/s, duration s) over the
        proposal->commit window."""
        if not self.commits:
            return 0.0, 0.0
        start = min(self.proposals.values())
        end = max(self.commits.values())
        duration = max(end - start, 1e-9)
        return self.committed_payloads() / duration, duration

    def has_window(self) -> bool:
        """True when the run produced a real measurement window (at least
        one commit) — failed runs must not be appended to results files
        (the aggregator means every block in a file)."""
        return bool(self.commits)

    def consensus_latency(self) -> float:
        """Mean proposal->commit latency (s) over PAYLOAD-CARRYING
        blocks — the reference's population (its latency is per batch
        digest, logs.py:157-159, and every upstream block carries a
        batch).  This framework also creates deliberately EMPTY blocks
        to drive the 2-chain commit of in-flight payloads; an empty
        block's commit lag includes waiting for the producer's next
        burst (~25 ms at 20 bursts/s), which is pacing, not consensus
        work — averaging it in overstated the latency by ~2x (measured
        17.5 ms mean vs 9 ms payload-block p50 at 4 nodes / 1k)."""
        lat = [
            self.commits[b] - self.proposals[b]
            for b in self.commits
            if self.block_payloads.get(b)
        ]
        return mean(lat) if lat else 0.0

    def end_to_end_throughput(self) -> tuple[float, float]:
        if not self.commits or self.client_start is None:
            return 0.0, 0.0
        end = max(self.commits.values())
        duration = max(end - self.client_start, 1e-9)
        return self.committed_payloads() / duration, duration

    def _sample_latencies(self) -> list[float]:
        """Send -> containing-block commit latency (s) per committed
        sample payload."""
        lat = []
        for payload, sent in self.samples.items():
            block = self.payload_to_block.get(payload)
            if block is not None and block in self.commits:
                lat.append(self.commits[block] - sent)
        return lat

    def end_to_end_latency(self) -> float | None:
        """Mean sample-payload send -> containing-block commit latency (s).
        None when no sample payload landed in the window — reporting 0 ms
        for "no data" would read as a (great) measurement."""
        lat = self._sample_latencies()
        return mean(lat) if lat else None

    def end_to_end_latency_percentiles(self) -> tuple[float, float] | None:
        """(p50, p99) over the sample-latency population (s), or None
        without committed samples.  Nearest-rank on the sorted
        latencies: the population is small (one tagged sample per
        burst), so interpolation would manufacture precision the
        samples don't carry."""
        lat = sorted(self._sample_latencies())
        if not lat:
            return None

        def rank(p: float) -> float:
            import math

            return lat[min(len(lat) - 1, math.ceil(p * len(lat)) - 1)]

        return rank(0.50), rank(0.99)

    def commit_round_gap(self) -> tuple[float, int] | None:
        """(mean, max) gap between consecutive COMMITTED rounds, or None
        without >= 2 committed rounds.  A gap of 1 is the steady state;
        larger gaps count the rounds lost to view changes — the
        liveness-cost view the storm benches exist to measure."""
        rounds = sorted(
            {self.block_round[b] for b in self.commits if b in self.block_round}
        )
        if len(rounds) < 2:
            return None
        gaps = [b - a for a, b in zip(rounds, rounds[1:])]
        return mean(gaps), max(gaps)

    def epoch_boundary_gap(self) -> int | None:
        """Max commit-round gap across any observed epoch boundary: for
        each activation round A, first committed round >= A minus last
        committed round < A.  None without an observed boundary (or any
        straddling commits) — the handoff-bound proof line for
        reconfiguration runs."""
        if not self.epoch_activations:
            return None
        rounds = sorted(
            {self.block_round[b] for b in self.commits if b in self.block_round}
        )
        gaps = []
        for acts in self.epoch_activations.values():
            for boundary in acts:
                before = [r for r in rounds if r < boundary]
                after = [r for r in rounds if r >= boundary]
                if before and after:
                    gaps.append(after[0] - before[-1])
        return max(gaps) if gaps else None

    def result(
        self,
        faults: int = 0,
        nodes: int | None = None,
        verifier: str = "cpu",
        extra: str = "",
    ) -> str:
        c_tps, c_dur = self.consensus_throughput()
        e_tps, _ = self.end_to_end_throughput()
        e2e_lat = self.end_to_end_latency()
        e2e_lat_txt = (
            f"{round(e2e_lat * 1000)} ms" if e2e_lat is not None
            else "n/a (no sample payload committed in the window)"
        )
        pcts = self.end_to_end_latency_percentiles()
        e2e_pct_txt = (
            f" End-to-end latency p50/p99:"
            f" {round(pcts[0] * 1000)} / {round(pcts[1] * 1000)} ms\n"
            if pcts is not None
            else ""
        )
        # the latency population is payload-carrying blocks (see
        # consensus_latency): a window with only empty 2-chain-driver
        # commits must print n/a, never a flattering 0 ms
        has_payload_commits = any(
            self.block_payloads.get(b) for b in self.commits
        )
        c_lat_txt = (
            f"{round(self.consensus_latency() * 1000)} ms"
            if has_payload_commits
            else "n/a (no payload-carrying commits)"
        )
        # Byte throughput (reference logs.py:147-169 reports BPS): the
        # committed-payload rate times the measured body size.  Only
        # meaningful when the client sent real bodies (tx_size > 0).
        if self.tx_size:
            c_bps_txt = f" Consensus BPS: {round(c_tps * self.tx_size):,} B/s\n"
            e_bps_txt = f" End-to-end BPS: {round(e_tps * self.tx_size):,} B/s\n"
        else:
            c_bps_txt = " Consensus BPS: n/a (digest-only payloads)\n"
            e_bps_txt = " End-to-end BPS: n/a (digest-only payloads)\n"
        return (
            "\n"
            "-----------------------------------------\n"
            " SUMMARY:\n"
            "-----------------------------------------\n"
            " + CONFIG:\n"
            f" Faults: {faults} node(s)\n"
            f" Committee size: {nodes if nodes is not None else '?'} node(s)\n"
            f" Input rate: {self.input_rate or 0} tx/s\n"
            f" Transaction size: {self.tx_size} B\n"
            f" Verifier backend: {verifier}\n"
            f" Consensus timeout delay: {self.timeout_delay or 0} ms\n"
            f" Execution time: {round(c_dur)} s\n"
            "\n"
            " + RESULTS:\n"
            f" Consensus TPS: {round(c_tps)} payloads/s\n"
            + c_bps_txt
            + f" Consensus latency: {c_lat_txt}\n"
            f" End-to-end TPS: {round(e_tps)} payloads/s\n"
            + e_bps_txt
            + f" End-to-end latency: {e2e_lat_txt}\n"
            + e2e_pct_txt
            + f" Committed blocks: {len(self.commits)}\n"
            f" View-change timeouts: {self.timeouts}\n"
            + self._round_gap_txt()
            + self._epoch_txt()
            + f" Client rate warnings: {self.rate_warnings}\n"
            + self._verify_stats_txt()
            + self._telemetry_breakdown_txt()
            + self._health_txt()
            + self._net_txt()
            + extra
            + "-----------------------------------------\n"
        )

    def _round_gap_txt(self) -> str:
        gap = self.commit_round_gap()
        if gap is None:
            return ""
        gap_mean, gap_max = gap
        return (
            f" Commit round gap: mean {gap_mean:.2f}, max {gap_max}"
            " (1.00 = no rounds lost)\n"
        )

    def _epoch_txt(self) -> str:
        """Epoch-transition lines (only for runs that crossed a live
        reconfiguration boundary): which epochs activated where, and the
        worst commit-round gap across any boundary — the handoff cost
        the reconfig chaos scenarios bound."""
        if not self.epoch_activations:
            return ""
        transitions = ", ".join(
            f"epoch {e} at round"
            f" {'/'.join(str(r) for r in sorted(rounds))}"
            for e, rounds in sorted(self.epoch_activations.items())
        )
        out = (
            f" Epoch transitions: {len(self.epoch_activations)}"
            f" ({transitions})\n"
        )
        gap = self.epoch_boundary_gap()
        if gap is not None:
            out += f" Max commit gap across a boundary: {gap} round(s)\n"
        return out

    def _verify_stats_txt(self) -> str:
        """Routing-split lines (only for runs with async verify services
        — the device-routing proof for tpu-verifier A/Bs)."""
        total = self.device_sigs + self.cpu_route_sigs
        if not total and not self.agg_claims:
            return ""
        pct = 100.0 * self.device_sigs / total if total else 0.0
        ewma = (
            f"{self.verify_ewma_ms:.1f} ms"
            if self.verify_ewma_ms is not None
            else "n/a"
        )
        out = (
            f" Verify sigs device-routed: {self.device_sigs:,} of {total:,}"
            f" ({pct:.0f}%)\n"
            f" Verify deadline misses: {self.deadline_misses}\n"
            f" Verify dispatch EWMA (worst service): {ewma}\n"
        )
        # per-route wave split (ISSUE 5): route flapping shows up here
        # as a device/cpu share that moves across rates
        waves = sum(self.route_waves.values())
        if waves:
            shares = "/".join(
                f"{r} {100.0 * n / waves:.0f}%"
                for r, n in self.route_waves.items()
            )
            depth = (
                f", pipeline depth {self.pipeline_depth}"
                if self.pipeline_depth
                else ""
            )
            out += (
                f" Verify route waves: {shares} of {waves:,}"
                f" (queued {self.pipeline_waits}{depth})\n"
            )
        # zero-copy ingest hit rate (ISSUE 20): of the waves that
        # touched the native staging arenas, how many were adopted
        # without the Python flatten hop
        zc_total = self.zero_copy_waves + self.ingest_fallback_waves
        if zc_total:
            out += (
                f" Verify zero-copy ingest: {self.zero_copy_waves:,} of"
                f" {zc_total:,} vote waves adopted"
                f" ({100.0 * self.zero_copy_waves / zc_total:.0f}%"
                f" hit rate)\n"
            )
        # aggregate-certificate route (ISSUE 9): compact QCs/TCs served
        # by one pairing each instead of per-signature batches
        if self.agg_claims:
            out += (
                f" Verify aggregate certificates: {self.agg_claims:,}"
                f" (standing in for {self.agg_claim_sigs:,} sigs,"
                f" one pairing each)\n"
            )
        if self.qc_wire_bytes:
            form = (
                f"{self.compact_qcs:,} compact QCs emitted"
                if self.compact_qcs
                else "vote-list form"
            )
            out += (
                f" QC wire size (last emitted): {self.qc_wire_bytes:,} B"
                f" ({form})\n"
            )
        return out

    def _health_txt(self) -> str:
        """The ``+ HEALTH`` block (only for runs with the health plane
        on): per-detector incident counts plus the SLO burn — the
        fraction of monitored node-time spent inside an open incident.
        Incidents still open at the end of the log burn until the last
        observed event."""
        if not self.health_nodes and not self.health_events:
            return ""
        lines = [" + HEALTH (anomaly detectors):\n"]
        lines.append(f" Nodes monitored: {self.health_nodes}\n")
        opens: Counter = Counter()
        open_at: dict[tuple[str, str], float] = {}
        spans: list[tuple[tuple[str, str], float, float]] = []
        for t, doc in self.health_events:
            key = (doc.get("node", ""), doc.get("kind", "?"))
            if doc.get("phase") == "open":
                opens[doc.get("kind", "?")] += 1
                open_at.setdefault(key, t)
            elif key in open_at:
                spans.append((key, open_at.pop(key), t))
        if open_at:
            end = max(
                [t for t, _ in self.health_events]
                + list(self.commits.values())
            )
            for key, t0 in open_at.items():
                spans.append((key, t0, end))
        if opens:
            shown = ", ".join(
                f"{kind} x{c}" if c > 1 else kind
                for kind, c in sorted(opens.items())
            )
            lines.append(
                f" Incidents: {sum(opens.values())} ({shown})\n"
            )
            worst = max(spans, key=lambda s: s[2] - s[1], default=None)
            if worst is not None:
                (node, kind), t0, t1 = worst
                lines.append(
                    f" Longest incident: {kind} on {node or '?'}"
                    f" ({t1 - t0:.1f} s)\n"
                )
        else:
            lines.append(" Incidents: 0\n")
        _, c_dur = self.consensus_throughput()
        if c_dur and self.health_nodes:
            burn = sum(t1 - t0 for _, t0, t1 in spans) / (
                c_dur * self.health_nodes
            )
            lines.append(
                f" SLO burn: {100.0 * min(burn, 1.0):.1f}% of monitored"
                " node-time inside an open incident\n"
            )
        return "".join(lines)

    def net_summary(self) -> dict | None:
        """Committee-wide wire flow rollup (ISSUE 19), or None when no
        node exported an ENABLED flows section.  The SUMMARY's
        ``+ NET`` block and the scaling table read this instead of
        re-deriving it from raw snapshots."""
        live = [f for f in self.flow_docs if f.get("enabled")]
        if not live:
            return None
        tx = sum(f.get("tx_bytes", 0) for f in live)
        rx = sum(f.get("rx_bytes", 0) for f in live)
        cls_tx: dict[str, int] = {}
        cls_fr: dict[str, int] = {}
        for f in live:
            for cls, ent in (f.get("classes") or {}).items():
                cls_tx[cls] = cls_tx.get(cls, 0) + ent.get("tx_bytes", 0)
                cls_fr[cls] = cls_fr.get(cls, 0) + ent.get("tx_frames", 0)
        amps = sorted(
            a
            for f in live
            for a in [(f.get("amp") or {}).get("propose")]
            if a
        )

        def pct(p: float) -> float:
            import math

            return amps[min(len(amps) - 1, math.ceil(p * len(amps)) - 1)]

        return {
            "nodes": len(live),
            "tx_bytes": tx,
            "rx_bytes": rx,
            "retx_bytes": sum(f.get("retx_bytes", 0) for f in live),
            "retx_frames": sum(f.get("retx_frames", 0) for f in live),
            "peers_elided": sum(f.get("peers_elided", 0) for f in live),
            "class_tx_bytes": cls_tx,
            "class_tx_frames": cls_fr,
            "leader_amp_p50": pct(0.50) if amps else None,
            "leader_amp_p99": pct(0.99) if amps else None,
            "wire_bytes_per_commit": (
                round(tx / len(self.commits)) if self.commits else None
            ),
        }

    def _net_txt(self) -> str:
        """The ``+ NET`` block (wire-level flow accounting, ISSUE 19):
        committee-wide egress/ingress, wire bytes per commit, per-class
        egress shares (they sum to 100% of accounted bytes — every
        frame lands in exactly one class), propose-amplification
        percentiles across nodes, retransmit overhead, and the
        compact-QC-on-wire vs vote-list-equivalent comparison."""
        if not self.flow_docs:
            return ""
        net = self.net_summary()
        lines = [" + NET (wire flow accounting):\n"]
        if net is None:
            lines.append(
                " Flow accounting: n/a (disabled — HOTSTUFF_NET=0)\n"
            )
            return "".join(lines)
        tx, rx = net["tx_bytes"], net["rx_bytes"]
        _, dur = self.consensus_throughput()
        rate_txt = f" ({round(tx / dur):,} B/s)" if dur and tx else ""
        lines.append(
            f" Wire egress: {tx:,} B across {net['nodes']}"
            f" node(s){rate_txt}\n"
        )
        lines.append(f" Wire ingress: {rx:,} B\n")
        wpc = net["wire_bytes_per_commit"]
        lines.append(
            f" Wire bytes per commit: {wpc:,} B egress"
            f" ({len(self.commits)} commits)\n"
            if wpc is not None
            else " Wire bytes per commit: n/a (no commits in the window)\n"
        )
        if tx:
            for cls, b in sorted(
                net["class_tx_bytes"].items(), key=lambda e: (-e[1], e[0])
            ):
                if b:
                    lines.append(
                        f" Class {cls + ':':<13} {b:>12,} B egress"
                        f" ({100.0 * b / tx:5.1f}%)\n"
                    )
        if net["leader_amp_p50"] is not None:
            lines.append(
                f" Propose amplification p50/p99:"
                f" {net['leader_amp_p50']:.2f} /"
                f" {net['leader_amp_p99']:.2f}"
                " (wire/logical egress; broadcast fan-out = n-1)\n"
            )
        if tx:
            lines.append(
                f" Retransmit overhead: {net['retx_bytes']:,} B"
                f" ({100.0 * net['retx_bytes'] / tx:.2f}% of egress,"
                f" {net['retx_frames']} frame(s))\n"
            )
        # compact-QC on-wire proof: the last emitted QC's wire size vs
        # what a quorum of individual votes costs on this run's links
        # (mean accounted vote frame x 2f+1)
        vote_b = net["class_tx_bytes"].get("vote", 0)
        vote_f = net["class_tx_frames"].get("vote", 0)
        if self.qc_wire_bytes and vote_f:
            quorum = self.num_node_logs - (self.num_node_logs - 1) // 3
            votelist = round(quorum * vote_b / vote_f)
            form = "compact" if self.compact_qcs else "vote-list"
            lines.append(
                f" QC on-wire ({form}): {self.qc_wire_bytes:,} B vs"
                f" ~{votelist:,} B as a {quorum}-vote list\n"
            )
        if net["peers_elided"]:
            lines.append(
                f" Peer gauges elided: {net['peers_elided']}"
                " (beyond top-K export; counted, never silent)\n"
            )
        return "".join(lines)

    def _telemetry_breakdown_txt(self) -> str:
        """Commit-latency breakdown from the per-node telemetry
        snapshots (only for runs with telemetry enabled): where a
        committed block's wall time went — the network/aggregation edges
        of its lifecycle, plus host-dispatch vs device verify wall and
        event-loop lag as per-commit attribution lines."""
        docs = self.telemetry_docs
        if not docs:
            return ""

        def edge_stats(edge: str):
            """Count-weighted mean and worst p99 across nodes, or None
            when no node recorded the edge."""
            entries = [
                d.get("trace", {}).get("edges", {}).get(edge, {})
                for d in docs
            ]
            entries = [e for e in entries if e.get("count")]
            total = sum(e["count"] for e in entries)
            if not total:
                return None
            mean_ms = sum(e["mean_ms"] * e["count"] for e in entries) / total
            p99_ms = max(e.get("p99_ms", 0.0) for e in entries)
            return total, mean_ms, p99_ms

        rows = []
        for edge, label in (
            ("propose_to_vote", "propose -> first-vote (net + verify)"),
            ("vote_to_qc", "first-vote -> QC (aggregation)"),
            ("qc_to_commit", "QC -> commit (2-chain)"),
            ("propose_to_commit", "propose -> commit (total)"),
        ):
            s = edge_stats(edge)
            if s is not None:
                count, mean_ms, p99_ms = s
                rows.append(
                    f" {label + ':':<40} mean {mean_ms:7.1f} ms"
                    f"  p99 {p99_ms:7.1f} ms  (n={count})\n"
                )
        if not rows:
            return ""
        commits = sum(d.get("trace", {}).get("commits", 0) for d in docs)
        attribution = []
        host_wall_ms = sum(d.get("verify_wall_ms", 0.0) for d in docs)
        if commits and host_wall_ms:
            attribution.append(
                f"host verify {host_wall_ms / commits:.2f} ms/commit"
            )
        if self.verify_ewma_ms is not None:
            attribution.append(
                f"device dispatch EWMA {self.verify_ewma_ms:.1f} ms"
            )
        lags = [
            d["loop_lag_mean_ms"] for d in docs if "loop_lag_mean_ms" in d
        ]
        if lags:
            attribution.append(f"loop lag mean {mean(lags):.2f} ms")
        txt = " + COMMIT LATENCY BREAKDOWN (telemetry):\n" + "".join(rows)
        if attribution:
            txt += " Attribution: " + ", ".join(attribution) + "\n"
        return txt
