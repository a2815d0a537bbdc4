"""Committee-scaling decomposition: protocol cost vs host starvation.

VERDICT r2 weak #4: the 1-core dev rig cannot host >=16 node processes,
so raw committee-size sweeps measure host starvation, not protocol
cost, while the reference publishes 50-node data from one-host-per-node
EC2.  This harness produces the best evidence this environment allows:

- an in-process sweep (one asyncio loop hosting the whole committee —
  OS scheduling excluded) with per-node work accounting
  (crypto/service.py VerifyWork: signature verifies, crypto wall time;
  telemetry/hoststats.py: event-loop lag — the direct starvation
  signal);
- a decomposition table: measured TPS, aggregate crypto work, loop lag,
  and the per-(node, payload) protocol cost c = core_seconds /
  (payloads * nodes) — every node processes every block, so ONE core
  hosting n nodes sustains ~1/(c*n) payloads/s while n DEDICATED cores
  (the reference's topology) sustain ~1/c per node, i.e. committee size
  costs latency, not throughput, until the leader's own core saturates;
- the multi-host prediction derived from that cost, printed alongside
  the starved single-core measurements so nobody mistakes one for the
  other.

Output: a table on stdout + ``results/scaling-decomposition.txt``.
"""

from __future__ import annotations

import json
import os
import re
from glob import glob

from .local import LocalBench
from .logs import LogParser
from .utils import PathMaker, Print

RE_HOST_STATS = re.compile(r"Host stats: (.*)")
RE_TELEMETRY = re.compile(r"Telemetry snapshot: (\{.*\})")


def scrape_host_stats(logs_dir: str) -> list[dict]:
    """Last 'Host stats' line (telemetry/hoststats.py: one a process,
    cumulative ``key=value`` pairs) of each node log, as floats."""
    latest: dict[str, dict] = {}
    for path in sorted(glob(os.path.join(logs_dir, "node-*.log"))):
        with open(path) as f:
            for line in f:
                m = RE_HOST_STATS.search(line)
                if m:
                    latest[path] = {
                        k: float(v)
                        for k, v in (
                            item.split("=") for item in m.group(1).split()
                        )
                    }
    return list(latest.values())


def scrape_telemetry(logs_dir: str) -> list[dict]:
    """Last 'Telemetry snapshot' document per node across the node logs.
    It carries the node's verification work and the process's loop lag
    at its top level (telemetry.SNAPSHOT_WORK_KEYS)."""
    latest: dict[tuple, dict] = {}
    for path in sorted(glob(os.path.join(logs_dir, "node-*.log"))):
        with open(path) as f:
            for line in f:
                m = RE_TELEMETRY.search(line)
                if not m:
                    continue
                try:
                    doc = json.loads(m.group(1))
                except ValueError:
                    continue  # truncated log line mid-write
                latest[(path, doc.get("node"))] = doc
    return list(latest.values())


def run_scaling(
    sizes=(4, 8, 16, 32),
    rate: int = 1_000,
    duration: float = 20.0,
    timeout_delay: int = 5_000,
    verifier: str = "cpu",
) -> str:
    # Telemetry snapshots are the work-accounting source; the loop lag
    # comes from every process's own 'Host stats' line, which is
    # printed whether telemetry is on or off
    os.environ["HOTSTUFF_TELEMETRY"] = "1"
    rows = []
    try:
        for n in sizes:
            bench = LocalBench(
                nodes=n,
                rate=rate,
                duration=duration,
                timeout_delay=timeout_delay,
                in_process=True,
                verifier=verifier,
            )
            parser: LogParser = bench.run()
            stats = scrape_telemetry(PathMaker.logs_path())
            hosts = scrape_host_stats(PathMaker.logs_path())
            tps, window = parser.consensus_throughput()
            lat_s = parser.consensus_latency()
            payloads = parser.committed_payloads()
            verify_sigs = sum(s.get("verify_sigs", 0) for s in stats)
            verify_wall_s = (
                sum(s.get("verify_wall_ms", 0.0) for s in stats) / 1e3
            )
            lag_means = [h.get("lag_mean_ms", 0.0) for h in hosts]
            rows.append(
                {
                    "nodes": n,
                    "tps": tps,
                    "latency_ms": lat_s * 1e3,
                    "payloads": payloads,
                    "window_s": window,
                    "verify_sigs": verify_sigs,
                    "verify_wall_s": verify_wall_s,
                    "loop_lag_mean_ms": (
                        sum(lag_means) / len(lag_means) if lag_means else 0.0
                    ),
                    "stats_nodes": len(stats),
                    # dispatch-wave routing split (ISSUE 5): scraped
                    # from the verify-service stats lines, so route
                    # flapping is visible per rate in the SUMMARY
                    "route_waves": dict(parser.route_waves),
                    "pipeline_waits": parser.pipeline_waits,
                    # zero-copy ingest split (ISSUE 20): arena-adopted
                    # waves vs. flatten fallbacks on vote waves
                    "zero_copy_waves": parser.zero_copy_waves,
                    "ingest_fallback_waves": parser.ingest_fallback_waves,
                    # compact-certificate columns (ISSUE 9): last emitted
                    # QC wire size plus how many certificates took the
                    # aggregate one-pairing route
                    "qc_bytes": parser.qc_wire_bytes or 0,
                    "agg_claims": parser.agg_claims,
                    "compact_qcs": parser.compact_qcs,
                    # ingest-plane columns (ISSUE 10): admission sheds
                    # and silent proposer drops, committee-wide — the
                    # second is nonzero only when backpressure failed
                    "ingest_shed": sum(
                        (s.get("ingest") or {}).get("shed_total", 0)
                        for s in stats
                    ),
                    "ingest_drops": sum(
                        (s.get("ingest") or {}).get("drop_newest", 0)
                        for s in stats
                    ),
                    # wire-flow columns (ISSUE 19): committee-wide wire
                    # egress and the median propose-amplification factor
                    # (n-1 when every proposal is one broadcast)
                    "net_tx_bytes": (parser.net_summary() or {}).get(
                        "tx_bytes", 0
                    ),
                    "net_amp_p50": (parser.net_summary() or {}).get(
                        "leader_amp_p50"
                    ),
                    # live-reconfiguration column (ISSUE 14): the newest
                    # epoch the committee activated during the window
                    # (1 = static committee, the sweep's normal state)
                    "epoch": (
                        max(parser.epoch_activations)
                        if parser.epoch_activations
                        else 1
                    ),
                }
            )
    finally:
        os.environ.pop("HOTSTUFF_TELEMETRY", None)
    return format_report(rows, rate, duration, verifier=verifier)


def format_report(
    rows: list[dict], rate: int, duration: float, verifier: str = "cpu"
) -> str:
    lines = [
        "COMMITTEE-SCALING DECOMPOSITION (in-process, one core, "
        f"{rate}/s input, {duration:.0f}s, verifier={verifier})",
        "",
        f"{'nodes':>6} {'epoch':>5} {'tps':>7} {'lat ms':>7} {'sigs/s':>8} "
        f"{'crypto s':>9} {'lag ms':>7} {'c us':>7} {'route d/c/p/m':>13} "
        f"{'zc%':>4} {'qc B':>6} {'agg':>5} {'shed':>6} {'dropN':>5} "
        f"{'net MB':>7} {'amp':>5} {'pred 1-core/node':>17}",
    ]
    for r in rows:
        window = max(r["window_s"], 1e-9)
        sig_rate = r["verify_sigs"] / window
        # per-(node, payload) protocol cost: the whole committee shares
        # ONE core in-process, so core-seconds ~= wall window; every
        # node processes every payload's block/QC once
        events = max(r["payloads"] * r["nodes"], 1)
        c_us = window / events * 1e6
        predicted = 1e6 / c_us  # payloads/s with a dedicated core/node
        waves = r.get("route_waves") or {}
        total_waves = sum(waves.values())
        if total_waves:
            route = "/".join(
                f"{100 * waves.get(k, 0) // total_waves}"
                for k in ("device", "cpu", "probe", "mesh")
            )
        else:
            route = "-"
        zc = r.get("zero_copy_waves", 0)
        zc_total = zc + r.get("ingest_fallback_waves", 0)
        zc_txt = f"{100 * zc // zc_total}" if zc_total else "-"
        qc_bytes = r.get("qc_bytes", 0)
        qc_txt = f"{qc_bytes}" if qc_bytes else "-"
        agg_claims = r.get("agg_claims", 0)
        agg_txt = f"{agg_claims}" if agg_claims else "-"
        shed = r.get("ingest_shed", 0)
        shed_txt = f"{shed}" if shed else "-"
        drops = r.get("ingest_drops", 0)
        drops_txt = f"{drops}" if drops else "-"
        net_tx = r.get("net_tx_bytes", 0)
        net_txt = f"{net_tx / 1e6:.1f}" if net_tx else "-"
        amp = r.get("net_amp_p50")
        amp_txt = f"{amp:.1f}" if amp else "-"
        lines.append(
            f"{r['nodes']:>6} {r.get('epoch', 1):>5} "
            f"{r['tps']:>7.0f} {r['latency_ms']:>7.0f} "
            f"{sig_rate:>8.0f} {r['verify_wall_s']:>9.2f} "
            f"{r['loop_lag_mean_ms']:>7.2f} {c_us:>7.0f} {route:>13} "
            f"{zc_txt:>4} {qc_txt:>6} {agg_txt:>5} {shed_txt:>6} {drops_txt:>5} "
            f"{net_txt:>7} {amp_txt:>5} {predicted:>17.0f}"
        )
    lines += [
        "",
        "READING THE TABLE",
    ]
    if verifier != "cpu":
        lines += [
            "- sigs/crypto read 0 under --verifier tpu: the async claims "
            "path runs verification OFF the counted loop (that is the "
            "point); use verifier=cpu for on-loop crypto accounting;"
        ]
    lines += [
        "- tps/lat: the starved single-core measurement (NOT protocol "
        "capability beyond ~8 nodes);",
        "- epoch: the newest committee epoch activated in the window "
        "(1 = no live reconfiguration, the sweep's normal state);",
        "- lag ms: mean event-loop scheduling lag — starvation onset is "
        "visible as lag >> 1 ms;",
        "- c us: measured per-(node, payload) protocol cost = "
        "window / (payloads x nodes) core-microseconds;",
        "- zc%: zero-copy ingest hit rate — vote waves the verify "
        "service adopted straight from a native staging arena as a "
        "share of arena-touching waves (adopted + flatten fallbacks; "
        "'-' for non-native transports or pre-ingest logs);",
        "- qc B / agg: last emitted QC's wire size and certificates "
        "served by the aggregate one-pairing route (BLS compact form: "
        "48 B agg sig + ceil(n/8) B signer bitmap vs n x 144 B vote "
        "lists; '-' for ed25519 vote-list committees);",
        "- shed / dropN: payloads the ingest plane shed with a typed "
        "BUSY reply vs payloads SILENTLY dropped at the full proposer "
        "buffer — dropN must stay '-' whenever admission control is "
        "doing its job (docs/LOAD.md);",
        "- net MB / amp: committee-wide wire egress (flow accounting, "
        "HOTSTUFF_NET) and the median propose-amplification factor — "
        "wire/logical egress bytes, n-1 when every proposal is exactly "
        "one broadcast ('-' with accounting disabled);",
        "- pred: payloads/s one node sustains on a DEDICATED core (the "
        "reference topology, one host per node) = 1/c.  Committee size "
        "multiplies the fleet's total work, not the per-node cost, so "
        "the predicted multi-host TPS holds roughly flat with committee "
        "size until the leader's own core saturates — matching the "
        "reference's flat 10->50-node WAN TPS "
        "(~100k tx/s, benchmark/data/2-chain/results/).",
    ]
    return "\n".join(lines)


def main(sizes, rate, duration, verifier="cpu") -> int:
    report = run_scaling(
        sizes=sizes, rate=rate, duration=duration, verifier=verifier
    )
    print(report)
    os.makedirs(PathMaker.results_path(), exist_ok=True)
    path = os.path.join(PathMaker.results_path(), "scaling-decomposition.txt")
    with open(path, "a") as f:
        f.write(report + "\n\n")
    Print.info(f"Report appended to {path}")
    return 0
