#!/usr/bin/env python3
"""Performance regression gate for the verify rig.

Compares a FRESH ``bench.py`` run against the committed reference
(latest ``BENCH_r*.json``, falling back to ``BASELINE.json``) and exits
non-zero when either guarded metric regresses past the threshold
(default 15%):

  * ``qc_verify_ms.256.rig_p50_ms``  — QC-256 end-to-end verify latency
    (the number the span waterfall decomposes; may not rise >15%)
  * ``value``                        — batch-1024 verify throughput in
    sigs/s (may not fall >15%)
  * ``pipeline.train_sigs_per_s``    — sustained QC-256 wave-train
    throughput through the depth-2 dispatch pipeline (ISSUE 5; may not
    fall >15%)
  * ``agg_qc.verify_p50_ms`` — compact-QC one-pairing verify at the
    largest benched committee (ISSUE 9; per-guard 75% gate — the value
    is a single host pairing, so only a structural regression such as
    losing the key-sum memo or the native pairing should trip it)
  * ``state.apply_tx_s`` / ``state.sync_catchup_s`` — replicated
    execution-layer apply throughput and snapshot serve+adopt wall cost
    (ISSUE 11; wide per-guard 50% gates, skip-if-missing)
  * ``sim.rounds_per_s`` / ``sim.seeds_per_min`` — deterministic
    simulator sweep throughput (ISSUE 15; wide per-guard 50% gates,
    skip-if-missing)
  * ``adapt.schedules_per_min`` / ``adapt.fitness_evals_per_s`` —
    adaptive-adversary guided-search throughput (ISSUE 18; wide
    per-guard 50% gates, skip-if-missing)
  * ``net.leader_amp_p50`` / ``net.wire_bytes_per_commit`` —
    wire-level flow accounting rollup: median propose-amplification
    factor (gated in both directions — a fall means lost charges, a
    rise means redundant sends) and committee wire egress per commit
    (ISSUE 19; wide per-guard 50% gates, skip-if-missing)

Guards missing from either side are skipped, so old references gate
only the metrics they carry.

Usage:

    python scripts/perfgate.py                 # runs bench.py itself
    python scripts/perfgate.py --fresh out.txt # pre-captured output
    python scripts/perfgate.py --fresh -       # ... from stdin
    PERFGATE=1 scripts/trace.sh                # opt-in after a trace run

The comparison logic is import-safe pure functions so tests can drive
it without spawning a benchmark.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (human name, extractor, direction[, threshold]) — direction +1 means
#: "higher is a regression" (latency), -1 means "lower is a regression"
#: (throughput).  An optional 4th element overrides the run's threshold
#: for THAT guard.
GUARDS = (
    (
        "qc_verify_ms.256.rig_p50_ms",
        lambda doc: doc.get("qc_verify_ms", {}).get("256", {}).get(
            "rig_p50_ms"
        ),
        +1,
    ),
    ("value (sigs/s)", lambda doc: doc.get("value"), -1),
    (
        "pipeline.train_sigs_per_s",
        lambda doc: (doc.get("pipeline") or {}).get("train_sigs_per_s"),
        -1,
    ),
    # compact-QC verify (ISSUE 9): ONE pairing over the memoized key sum
    # at the largest benched committee.  Skip-if-missing covers
    # references predating the agg_qc block; the wide 75% per-guard gate
    # tolerates host pairing jitter while still catching a lost memo or
    # a fall off the native pairing path (both are >2x).
    (
        "agg_qc.verify_p50_ms",
        lambda doc: (doc.get("agg_qc") or {}).get("verify_p50_ms"),
        +1,
        0.75,
    ),
    # admission-controlled payload plane (ISSUE 10): committed goodput
    # and client-observed tail latency from a short loadgen run against
    # a live 4-node committee.  Both are end-to-end numbers through the
    # whole consensus stack on a shared single-core rig, so the
    # per-guard gates are wide; skip-if-missing covers references from
    # before the load block existed.
    (
        "load.goodput_tx_s",
        lambda doc: (doc.get("load") or {}).get("goodput_tx_s"),
        -1,
        0.5,
    ),
    (
        "load.client_p99_ms",
        lambda doc: (doc.get("load") or {}).get("client_p99_ms"),
        +1,
        0.75,
    ),
    # replicated execution layer (ISSUE 11): typed-op apply throughput
    # through StateMachine.apply_block and the wall cost of a full
    # snapshot serve+adopt cycle (the no-replay rejoin path).  Both run
    # on the WAL engine of a shared single-core rig, so the per-guard
    # gates are wide; skip-if-missing covers references from before the
    # state block existed.
    (
        "state.apply_tx_s",
        lambda doc: (doc.get("state") or {}).get("apply_tx_s"),
        -1,
        0.5,
    ),
    (
        "state.sync_catchup_s",
        lambda doc: (doc.get("state") or {}).get("sync_catchup_s"),
        +1,
        0.5,
    ),
    # deterministic simulator (ISSUE 15): how fast this host chews
    # through exploration seeds — consensus rounds simulated per wall
    # second and seeds per minute over a short sweep.  Whole-committee
    # Python on a shared single-core rig, so the per-guard gates are
    # wide; skip-if-missing covers references from before the sim block
    # existed.
    (
        "sim.rounds_per_s",
        lambda doc: (doc.get("sim") or {}).get("rounds_per_s"),
        -1,
        0.5,
    ),
    (
        "sim.seeds_per_min",
        lambda doc: (doc.get("sim") or {}).get("seeds_per_min"),
        -1,
        0.5,
    ),
    # commit critical-path attribution (ISSUE 17): end-to-end commit
    # latency p50 and attribution coverage from the journal-merged
    # critpath engine over a sim sweep.  Whole-committee Python on a
    # shared rig — wide gates; skip-if-missing covers references from
    # before the critpath block existed.  The attribution SHAPE (per
    # stage share) is gated separately by attribution_check() below —
    # a stage whose share of commit latency balloons fails the gate
    # even when these scalars hold.
    (
        "critpath.p50_ms",
        lambda doc: (doc.get("critpath") or {}).get("p50_ms"),
        +1,
        0.75,
    ),
    (
        "critpath.coverage_pct",
        lambda doc: (doc.get("critpath") or {}).get("coverage_pct"),
        -1,
        0.25,
    ),
    # adaptive-adversary guided search (ISSUE 18): candidate schedules
    # simulated per minute and fitness evaluations per second — the two
    # throughputs that bound how much schedule space a guided-search
    # budget actually covers.  Whole-committee Python on a shared
    # single-core rig, so the per-guard gates are wide; skip-if-missing
    # covers references from before the adapt block existed.
    (
        "adapt.schedules_per_min",
        lambda doc: (doc.get("adapt") or {}).get("schedules_per_min"),
        -1,
        0.5,
    ),
    (
        "adapt.fitness_evals_per_s",
        lambda doc: (doc.get("adapt") or {}).get("fitness_evals_per_s"),
        -1,
        0.5,
    ),
    # wire-level flow accounting (ISSUE 19): the median per-node
    # propose-amplification factor (wire/logical egress; exactly n-1
    # when every proposal is one broadcast — a FALL means charges went
    # missing, a RISE means redundant sends crept in, both regressions,
    # so the amp guard gates in both directions via two entries) and the
    # committee's wire egress per committed block.  Skip-if-missing
    # covers references from before the net block existed.
    (
        "net.leader_amp_p50",
        lambda doc: (doc.get("net") or {}).get("leader_amp_p50"),
        +1,
        0.5,
    ),
    (
        "net.leader_amp_p50 (floor)",
        lambda doc: (doc.get("net") or {}).get("leader_amp_p50"),
        -1,
        0.5,
    ),
    (
        "net.wire_bytes_per_commit",
        lambda doc: (doc.get("net") or {}).get("wire_bytes_per_commit"),
        +1,
        0.5,
    ),
    # zero-copy ingest throughput (ISSUE 20): sustained wire -> arena ->
    # device sigs/s through the native wave packer + verify_packed.
    # Skip-if-missing covers references from before the ingest block
    # existed and hosts without the native toolchain; the wide 50% gate
    # tolerates simulated-device noise while catching a fall off the
    # arena fast path (the flatten detour alone is >2x on large waves).
    (
        "ingest.zero_copy_sigs_per_s",
        lambda doc: (doc.get("ingest") or {}).get("zero_copy_sigs_per_s"),
        -1,
        0.5,
    ),
)

def last_json_line(text: str) -> dict | None:
    """The bench contract: the result is the LAST parseable JSON object
    line of stdout (jax warnings etc. precede it)."""
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict):
            return doc
    return None


def load_reference(repo: str = REPO) -> tuple[dict, str] | None:
    """Latest ``BENCH_r*.json``'s metrics (its ``parsed`` dict, or the
    JSON line inside ``tail``), else ``BASELINE.json`` if it carries
    published numbers.  Returns (metrics, source-path) or None."""
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json")),
                       reverse=True):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        doc = rec.get("parsed") or last_json_line(rec.get("tail", ""))
        if isinstance(doc, dict) and any(
            fn(doc) is not None for _, fn, *_ in GUARDS
        ):
            return doc, path
    base = os.path.join(repo, "BASELINE.json")
    try:
        with open(base) as f:
            doc = json.load(f).get("published") or {}
    except (OSError, ValueError):
        return None
    if any(fn(doc) is not None for _, fn, *_ in GUARDS):
        return doc, base
    return None


def attribution_check(fresh: dict, ref: dict) -> list[str]:
    """Attribution-shape gate: failure messages when any critical-path
    stage's SHARE of commit latency regressed past the engine tolerance
    (HOTSTUFF_CRITPATH_DIFF_PP) — the scalar-blind regression the plain
    guards cannot see.  Skip-if-missing on either side, and degrade to
    skip when the engine is unimportable (perfgate must run anywhere)."""
    f, r = fresh.get("critpath"), ref.get("critpath")
    if not isinstance(f, dict) or not isinstance(r, dict):
        return []
    try:
        sys.path.insert(0, REPO)
        from hotstuff_tpu.telemetry import critpath as engine

        from benchmark.critpath import diff_share_pp
    except Exception:  # noqa: BLE001 — shape gate is best-effort extra
        return []
    return [
        f"critpath attribution: {msg}"
        for msg in engine.diff(f, r, share_pp=diff_share_pp())
    ]


def compare(fresh: dict, ref: dict, threshold: float = 0.15) -> list[str]:
    """Failure messages for every guarded metric past the threshold.
    A metric missing on either side is skipped (a bench that stopped
    publishing a number is a review problem, not a perf gate's)."""
    failures = []
    for name, fn, direction, *rest in GUARDS:
        f, r = fn(fresh), fn(ref)
        if f is None or r is None or r <= 0:
            continue
        gate = rest[0] if rest else threshold
        delta = (f - r) / r * direction
        if delta > gate:
            word = "rose" if direction > 0 else "fell"
            failures.append(
                f"{name} {word} {abs(f - r) / r:.1%} past the "
                f"{gate:.0%} gate (fresh {f:g} vs reference {r:g})"
            )
    return failures


def run_bench(repo: str = REPO) -> str:
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench.py exited {proc.returncode}:\n{proc.stderr[-2000:]}"
        )
    return proc.stdout


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--fresh",
        default=None,
        metavar="FILE",
        help="pre-captured bench.py stdout ('-' for stdin) instead of "
        "running the benchmark",
    )
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="allowed relative regression (default 0.15)")
    args = ap.parse_args(argv)

    ref = load_reference()
    if ref is None:
        print("perfgate: no usable reference (BENCH_r*.json / "
              "BASELINE.json) — nothing to gate against")
        return 0
    ref_doc, ref_path = ref

    if args.fresh == "-":
        text = sys.stdin.read()
    elif args.fresh:
        with open(args.fresh) as f:
            text = f.read()
    else:
        print("perfgate: running bench.py ...")
        text = run_bench()
    fresh = last_json_line(text)
    if fresh is None:
        print("perfgate: FAIL — no JSON result line in the fresh bench "
              "output")
        return 1

    failures = compare(fresh, ref_doc, args.threshold)
    failures += attribution_check(fresh, ref_doc)
    rel = os.path.relpath(ref_path, REPO)
    if failures:
        print(f"perfgate: FAIL vs {rel}")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    checked = [n for n, fn, *_ in GUARDS
               if fn(fresh) is not None and fn(ref_doc) is not None]
    print(f"perfgate: OK vs {rel} ({', '.join(checked) or 'nothing'} "
          f"within {args.threshold:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
