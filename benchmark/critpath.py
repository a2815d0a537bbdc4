"""`python -m benchmark critpath` — commit critical-path attribution.

Thin CLI over the pure engine (hotstuff_tpu/telemetry/critpath.py):
merge a run's flight-recorder journals (benchmark/traces.py), attribute
every commit's latency to the registered stage taxonomy, and

- print the "+ CRITPATH" SUMMARY block (p50/p99 by stage, dominant-stage
  histogram, slowest edges, regime classification, journal coverage);
- re-export the Chrome trace with the dedicated "critical path" track
  highlighting each commit's winning chain;
- write the machine-readable attribution document (logs/critpath.json);
- with ``--diff REF.json``, gate on ATTRIBUTION SHAPE: exit nonzero when
  any stage's share of commit latency regressed beyond the tolerance
  (HOTSTUFF_CRITPATH_DIFF_PP percentage points, default 10) even if the
  scalar latency held.  REF may be a bench JSON document or a prior
  attribution document (a previously written critpath.json).
"""

from __future__ import annotations

import json
import os

from hotstuff_tpu.telemetry import critpath as engine

from .utils import PathMaker, Print


def diff_share_pp() -> float:
    """The --diff share tolerance in percentage points
    (HOTSTUFF_CRITPATH_DIFF_PP, default engine.DIFF_SHARE_PP)."""
    raw = os.environ.get("HOTSTUFF_CRITPATH_DIFF_PP", "").strip()
    try:
        return float(raw) if raw else engine.DIFF_SHARE_PP
    except ValueError:
        return engine.DIFF_SHARE_PP


def load_reference_attribution(path: str) -> dict | None:
    """Extract an attribution document from ``path``: a raw
    critpath.json ({"stages": ...}), a bench JSON doc with a "critpath"
    block, or a record that wraps one ({"parsed": {...}} /
    {"tail": "..."})."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict):
        return None
    if "stages" in doc:
        return doc
    if isinstance(doc.get("critpath"), dict):
        return doc["critpath"]
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and isinstance(
        parsed.get("critpath"), dict
    ):
        return parsed["critpath"]
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and isinstance(
                cand.get("critpath"), dict
            ):
                return cand["critpath"]
    return None


def analyze_dir(dir_path: str):
    """(TraceSet, CritPathReport) for the journals under ``dir_path``."""
    from .traces import TraceSet

    traces = TraceSet.load(dir_path)
    return traces, engine.analyze(traces)


def run_critpath(
    dir_path: str,
    out: str | None = None,
    diff_path: str | None = None,
    json_line: bool = False,
) -> int:
    """The ``benchmark critpath`` task body; returns the exit code."""
    traces, report = analyze_dir(dir_path)
    if not traces.journals:
        Print.error(f"no journal segments found under {dir_path}")
        return 1
    print(engine.render(report))
    att = report.attribution()
    doc_path = PathMaker.critpath_file()
    parent = os.path.dirname(doc_path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(doc_path, "w") as f:
        json.dump(att, f, sort_keys=True)
    Print.info(f"Attribution document written to {doc_path}")
    if out and traces.blocks:
        trace_out = traces.export_chrome_trace(out, critpath=report)
        Print.info(
            f"Chrome trace (critical-path track) written to {trace_out}"
        )
    if json_line:
        print(json.dumps({"critpath": att}))
    if diff_path is not None:
        ref = load_reference_attribution(diff_path)
        if ref is None:
            Print.warn(
                f"no reference attribution in {diff_path};"
                " diff skipped (skip-if-missing)"
            )
            return 0
        fails = engine.diff(att, ref, share_pp=diff_share_pp())
        if fails:
            Print.error(
                f"attribution regressed vs {diff_path}:"
            )
            for line in fails:
                print(f"   {line}")
            return 1
        Print.info(
            f"attribution shape holds vs {diff_path}"
            f" (tolerance {diff_share_pp():.1f}pp per stage)"
        )
    return 0


__all__ = [
    "analyze_dir",
    "diff_share_pp",
    "load_reference_attribution",
    "run_critpath",
]
