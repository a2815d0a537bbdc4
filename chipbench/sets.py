"""A set of runs of one cell, one seed each, in one call: how a cell's
spread is measured before its bound is set (``PERF.md``, section 2).

    python3 chipbench/sets.py --label low.a --workload colo64.low \\
        --seeds 2147483700,2147483701 --seconds 40 [--trace 1]

Every run's last line goes to ``chiprun_out/sets/<label>.jsonl`` with the
line of detail before it; every run's committee log is kept beside it,
packed.  The summary gives, for each metric, the median and the spread: the
distance between the quartiles (``statistics.quantiles(values, n=4)``)
as a share of the median.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import ending  # noqa: E402


def spread(values: list[float]) -> float | None:
    # a count that reads 0 in every run (view changes) has no share
    if len(values) < 2 or not statistics.median(values):
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--label", required=True)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    ending.exit_on_signals()
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    values: dict[str, list[float]] = {}
    for seed in args.seeds.split(","):
        began = time.time()
        run = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", str(args.seconds), "--trace", str(args.trace)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=ROOT,
        )
        try:
            stdout, stderr = run.communicate()
        finally:
            # ending a set ends its run, which ends its committee
            if run.poll() is None:
                run.terminate()
                run.wait()
        done = subprocess.CompletedProcess(
            run.args, run.returncode, stdout, stderr
        )
        lines = done.stdout.strip().splitlines()
        record = {
            "label": args.label, "seed": int(seed), "rc": done.returncode,
            "took_s": time.time() - began,
            "result": json.loads(lines[-1]) if lines else None,
            "detail": json.loads(lines[-2]) if len(lines) > 1 else None,
            "stderr": done.stderr[-2000:],
        }
        with open(os.path.join(out_dir, args.label + ".jsonl"), "a") as f:
            f.write(json.dumps(record) + "\n")
        # every run's committee log and device events are kept, packed
        run_dir = os.path.join(ROOT, "chiprun_out", "chipbench", args.workload)
        for name in ("node.log", "trace_events.json"):
            path = os.path.join(run_dir, name)
            if os.path.exists(path):
                with open(path, "rb") as src, gzip.open(
                    os.path.join(out_dir, f"{args.label}.{seed}.{name}.gz"),
                    "wb",
                ) as dst:
                    shutil.copyfileobj(src, dst)
                os.remove(path)
        result = record["result"] or {}
        print(json.dumps({
            "seed": int(seed), "rc": done.returncode,
            "took_s": round(record["took_s"], 1),
            "correct": result.get("correct"),
            "attempted": result.get("attempted"),
            "failed": result.get("failed"),
            "metrics": {
                k: v["value"] for k, v in result.get("metrics", {}).items()
            },
            "device": result.get("device"),
            "why_not": (record["detail"] or {}).get("why_not_correct"),
        }), flush=True)
        if done.returncode != 0:
            print(done.stderr[-1500:], flush=True)
        for name, metric in result.get("metrics", {}).items():
            values.setdefault(name, []).append(metric["value"])
    summary = {
        name: {
            "median": statistics.median(v), "spread": spread(v),
            # the first run of a call compiles: the rest, apart
            "spread_without_first": spread(v[1:]), "n": len(v),
        }
        for name, v in values.items()
    }
    print(json.dumps({"label": args.label, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
