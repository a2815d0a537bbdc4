#!/usr/bin/env python3
"""Wire-level flow accounting check (docs/TELEMETRY.md, ISSUE 19).

Drives the flow-accounting plane end-to-end and exits non-zero when ANY
contract breaks:

1. **Journaled run #1** — ``benchmark local --nodes 4 --journal``: the
   run must PASS, print the ``+ NET`` SUMMARY block, and the parsed
   flow ledgers must satisfy the acceptance floors: median propose
   amplification within 20% of n-1 (round-robin leaders broadcast every
   proposal to the other n-1 peers), per-class byte shares summing to
   >= 95% of accounted egress (less means frames are being charged to
   thin air), compact QCs cheaper on the wire than the quorum-sized
   vote list they replace, and ZERO retransmitted bytes on clean
   localhost links.
2. **Determinism** — the same honest sim schedule run twice must
   produce byte-identical per-node flow tables (the accounting rides
   the deterministic plane: same seed, same ledger, to the byte).
3. **Flapping-link chaos** — a sim schedule with sustained lossy links
   must still land propose amplification in a sane band (>= 1, and
   bounded by retransmit inflation); a lossy link CAN legitimately
   retransmit, so retx is reported, not gated, here.

Usage:
    python scripts/net_check.py [--rate R] [--duration D]
    NET=1 scripts/trace.sh                # same, via the trace wrapper
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: acceptance: median propose amplification within this fraction of n-1
AMP_TOLERANCE = 0.20

#: acceptance: per-class shares must cover this much of accounted egress
MIN_CLASS_COVERAGE = 0.95


def check(label: str, ok: bool, detail: str = "") -> bool:
    print(f"  [{'ok' if ok else 'FAIL'}] {label}"
          + (f" — {detail}" if detail and not ok else ""))
    return ok


def _run_local(rate: int, duration: int) -> tuple[int, str]:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["HOTSTUFF_NET"] = "1"  # the plane under test must be on
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark", "local",
         "--nodes", "4", "--rate", str(rate),
         "--duration", str(duration), "--journal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    return proc.returncode, proc.stdout + proc.stderr


def _amp_from_tables(flows: dict) -> list[float]:
    """Per-node propose amplification (wire/logical egress) from the
    sim verdict's flow tables."""
    amps = []
    for tables in flows.values():
        wire = logical = 0
        for table in tables:
            for key, row in (table.get("flows") or {}).items():
                _peer, d, cls = key.rsplit("|", 2)
                if d == "tx" and cls == "propose":
                    wire += row[0]
            row = (table.get("logical") or {}).get("propose")
            if row:
                logical += row[0]
        if logical:
            amps.append(wire / logical)
    return sorted(amps)


def _retx_from_tables(flows: dict) -> int:
    total = 0
    for tables in flows.values():
        for table in tables:
            for row in (table.get("flows") or {}).values():
                total += row[2]
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rate", type=int, default=500)
    ap.add_argument("--duration", type=int, default=10)
    args = ap.parse_args(argv)

    os.chdir(REPO)
    failed = False

    print("=== phase 1: journaled 4-node run, flow ledger floors ===")
    rc, out = _run_local(args.rate, args.duration)
    failed |= not check("run #1 PASSes (exit 0)", rc == 0, f"exit {rc}")
    failed |= not check("+ NET block in SUMMARY", "+ NET" in out)

    from benchmark.logs import LogParser
    from benchmark.utils import PathMaker

    parser = LogParser.process(PathMaker.logs_path())
    net = parser.net_summary()
    failed |= not check("flow accounting enabled on all nodes",
                        net is not None and net["nodes"] > 0)
    if net is None:
        print("net check: FAIL")
        return 1

    n = parser.num_node_logs
    amp, target = net["leader_amp_p50"], float(n - 1)
    failed |= not check(
        f"propose amp p50 within {AMP_TOLERANCE:.0%} of n-1={target:g}",
        amp is not None and abs(amp - target) <= AMP_TOLERANCE * target,
        f"amp p50 {amp}",
    )
    covered = sum(net["class_tx_bytes"].values())
    failed |= not check(
        f"class shares cover >= {MIN_CLASS_COVERAGE:.0%} of egress",
        net["tx_bytes"] > 0
        and covered >= MIN_CLASS_COVERAGE * net["tx_bytes"],
        f"{covered:,} of {net['tx_bytes']:,} B",
    )
    vote_b = net["class_tx_bytes"].get("vote", 0)
    vote_f = net["class_tx_frames"].get("vote", 0)
    quorum = n - (n - 1) // 3
    votelist = round(quorum * vote_b / vote_f) if vote_f else 0
    failed |= not check(
        "compact QC cheaper on the wire than the vote list it replaces",
        0 < parser.qc_wire_bytes < votelist,
        f"qc {parser.qc_wire_bytes:,} B vs vote list ~{votelist:,} B",
    )
    failed |= not check(
        "zero retransmitted bytes on clean localhost links",
        net["retx_bytes"] == 0,
        f"{net['retx_bytes']:,} retx B",
    )
    print(f"  (run #1: {net['tx_bytes']:,} B egress across {net['nodes']} "
          f"nodes, amp p50 {amp}, "
          f"{net['wire_bytes_per_commit']:,} B/commit)")

    print("=== phase 2: same-seed sim runs are byte-identical ===")
    from hotstuff_tpu.sim import draw_schedule, run_schedule

    schedule = draw_schedule(3, nodes=4, profile="honest")
    v1 = run_schedule(schedule)
    v2 = run_schedule(schedule)
    failed |= not check("sim run #1 PASSes", v1.ok)
    failed |= not check("flow tables harvested", bool(v1.flows))
    failed |= not check(
        "double-run flow tables byte-identical",
        json.dumps(v1.flows, sort_keys=True)
        == json.dumps(v2.flows, sort_keys=True),
    )

    print("=== phase 3: amp sanity under flapping-link chaos ===")
    flapping = {
        "version": schedule["version"],
        "seed": 11,
        "nodes": 4,
        "duration_s": 9.0,
        "profile": "honest",
        # two lossy links flapping across most of the run: enough to
        # force reconnect/retransmit churn without breaking liveness
        "events": [
            {"kind": "loss", "from": [0], "to": [1], "drop": 0.25,
             "at": 1.5, "until": 3.5},
            {"kind": "loss", "from": [2], "to": [3], "drop": 0.25,
             "at": 2.0, "until": 4.0},
            {"kind": "loss", "from": [0], "to": [1], "drop": 0.2,
             "at": 4.5, "until": 5.5},
        ],
    }
    v3 = run_schedule(flapping)
    failed |= not check("chaos run PASSes invariants", v3.ok)
    amps = _amp_from_tables(v3.flows)
    amp3 = amps[len(amps) // 2] if amps else None
    # retransmits inflate the wire side, never deflate it: sane means
    # at least broadcast-shaped and not runaway duplication
    failed |= not check(
        "propose amp sane under chaos (1 <= amp <= 3x(n-1))",
        amp3 is not None and 1.0 <= amp3 <= 3.0 * (4 - 1),
        f"amp p50 {amp3}",
    )
    retx = _retx_from_tables(v3.flows)
    print(f"  (chaos run: amp p50 {amp3 and round(amp3, 2)}, "
          f"{retx:,} retx B — informational)")

    print("net check:", "FAIL" if failed else "PASS")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
