#!/usr/bin/env python3
"""Live health-plane check (docs/TELEMETRY.md, ISSUE 13).

Two phases, exit non-zero when ANY contract breaks:

1. **Healthy committee, live watch** — a 4-node ``benchmark local
   --health --journal`` run with the fleet watcher attached mid-run:
   every node must scrape (no STALE rows), the head round must
   advance, the anomaly detectors must stay quiet (zero crit
   incidents, nothing open at the end), and the SUMMARY must carry the
   ``+ HEALTH`` block with all four monitors announced.
2. **Leader isolation trips leader-stall** — the canned
   ``leader-isolation`` chaos scenario with the watcher attached: a
   ``leader_stall`` incident must appear in the LIVE view (scraped
   from the victim's own monitor) and in the ``+ HEALTH`` SUMMARY
   block, and the campaign rings must persist beside the journals.

Usage:
    python scripts/health_check.py [--rate R]
    HEALTH=1 scripts/trace.sh             # same, via the trace wrapper
"""

from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))


def check(label: str, ok: bool, detail: str = "") -> bool:
    print(f"  [{'ok' if ok else 'FAIL'}] {label}"
          + (f" — {detail}" if detail and not ok else ""))
    return ok


def _launch(args: list[str]) -> subprocess.Popen:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "benchmark", *args],
        cwd=REPO,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def _attach(launched_at: float, boot_timeout: float = 60.0):
    """(targets, leader_order) once THIS run's committee files exist and
    the first node answers a /delta scrape."""
    from benchmark.utils import PathMaker
    from benchmark.watch import NodeFeed, fleet_targets

    deadline = time.time() + boot_timeout
    while time.time() < deadline:
        try:
            if os.path.getmtime(PathMaker.committee_file()) < launched_at:
                raise OSError("stale committee from a previous run")
            targets, order = fleet_targets()
            t = targets[0]
            probe = NodeFeed(t["name"], f"http://{t['host']}:{t['port']}")
            if probe.poll() is not None:
                return targets, order
        except (OSError, RuntimeError, ValueError):
            pass
        time.sleep(1.0)
    raise TimeoutError("committee metrics endpoints never came up")


def _watch(targets, order, timeout_s: float, duration: float):
    """Run the watcher for ``duration`` s; (final view, watcher)."""
    from benchmark.watch import FleetWatcher, run_watch

    frames: list[str] = []
    watcher = FleetWatcher(targets, order, timeout_s=timeout_s)
    view = run_watch(
        watcher, duration=duration, interval=1.0, out=frames.append
    )
    return view, watcher, frames


def phase_healthy(rate: int) -> bool:
    print("=== phase 1: healthy committee, live watch ===")
    failed = False
    launched_at = time.time()
    proc = _launch([
        "local", "--nodes", "4", "--rate", str(rate),
        "--duration", "25", "--health", "--journal",
    ])
    try:
        targets, order = _attach(launched_at)
        failed |= not check("watch attached to 4 nodes", len(targets) == 4,
                            f"found {len(targets)}")
        view, watcher, frames = _watch(
            targets, order, timeout_s=5.0, duration=10.0
        )
        live = [v for v in view["nodes"] if not v.get("stale")]
        failed |= not check("no STALE rows mid-run", len(live) == 4,
                            f"{4 - len(live)} stale")
        failed |= not check("head round advancing", view["head"] > 0,
                            f"head {view['head']}")
        rates = [v.get("commit_rate") for v in view["nodes"]]
        failed |= not check(
            "per-node commit rate measured",
            any(isinstance(r, float) and r > 0 for r in rates),
            f"rates {rates}",
        )
        crits = [i for _, i in watcher.incidents if i.severity == "crit"]
        failed |= not check("zero crit incidents on a healthy run",
                            not crits, f"{[(i.kind, i.node) for i in crits]}")
        failed |= not check("nothing open at watch end", not view["open"],
                            f"{view['open']}")
        if watcher.incidents:
            print(f"  (transient warns observed: "
                  f"{[(i.kind, i.node) for _, i in watcher.incidents]})")
        out, _ = proc.communicate(timeout=120)
        failed |= not check("run PASSes (exit 0)", proc.returncode == 0,
                            f"exit {proc.returncode}")
        failed |= not check("+ HEALTH block in SUMMARY", "+ HEALTH" in out)
        failed |= not check("all 4 monitors announced",
                            "Nodes monitored: 4" in out)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    return failed


def phase_isolation(rate: int) -> bool:
    print("=== phase 2: leader-isolation trips leader-stall ===")
    failed = False
    launched_at = time.time()
    proc = _launch([
        "chaos", "--scenario", "leader-isolation", "--seed", "7",
        "--rate", str(rate), "--duration", "10",
        "--timeout-delay", "1000", "--health", "--journal",
    ])
    try:
        targets, order = _attach(launched_at)
        # the scenario isolates one node for 7 s against a 1 s timeout:
        # its own monitor fires leader_stall (3 s threshold) and the
        # watcher must lift it into the live feed
        view, watcher, frames = _watch(
            targets, order, timeout_s=1.0, duration=45.0
        )
        live_kinds = {i.kind for _, i in watcher.incidents}
        failed |= not check("leader_stall in the LIVE view",
                            "leader_stall" in live_kinds,
                            f"live incidents {sorted(live_kinds)}")
        rendered = any("leader_stall" in f for f in frames)
        failed |= not check("incident rendered on the dashboard", rendered)
        out, _ = proc.communicate(timeout=120)
        failed |= not check("run PASSes (exit 0)", proc.returncode == 0,
                            f"exit {proc.returncode}")
        failed |= not check("+ HEALTH block in SUMMARY", "+ HEALTH" in out)
        failed |= not check("leader_stall in SUMMARY",
                            "leader_stall" in out)
        from benchmark.utils import PathMaker
        from hotstuff_tpu.telemetry.health import CAMPAIGN_SUFFIX

        rings = glob.glob(os.path.join(
            REPO, PathMaker.journals_path(), f"*{CAMPAIGN_SUFFIX}"))
        failed |= not check("campaign rings persisted", bool(rings))
        trace = os.path.join(REPO, PathMaker.trace_file())
        failed |= not check(
            "incidents track in the Chrome trace",
            os.path.exists(trace)
            and '"incidents"' in open(trace, errors="replace").read(),
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rate", type=int, default=400)
    args = ap.parse_args(argv)

    os.chdir(REPO)
    failed = phase_healthy(args.rate)
    failed |= phase_isolation(args.rate)
    print("health check:", "FAIL" if failed else "PASS")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
