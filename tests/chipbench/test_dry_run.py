"""The command end to end on the CPU (``--dry``: 4 nodes, the CPU
verifier, a few seconds), and the child without a TPU.

The 4-node committee (``configs/local4.json`` x ``traffic/fab1k.json``)
is measured but not a cell yet (``PERF.md``, section 7), so the dry run
adds it the way a later PR would: a copy of ``chipbench/`` with no file
changed, and new entries in a copy of ``BENCHMARK.json``."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "local4.fab1k"


def checkout_with_the_cell(tmp_path) -> str:
    shutil.copytree(
        os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "configs", "local4.json")) as f:
        config = json.load(f)
    bench["configs"].append({
        "name": "local4", "source": config["source"],
        "file": "chipbench/configs/local4.json",
        "reduced": config["reduced"], "why": "the dry run's committee",
    })
    bench["workloads"].append({
        "name": CELL, "config": "local4", "traffic": "fab1k", "chips": 1,
        "why": "the dry run's cell",
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append(CELL)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(tmp_path)


def test_dry_run_prints_the_contracts_last_line(tmp_path):
    checkout = checkout_with_the_cell(tmp_path)
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "2147483999", "--seconds", "3", "--trace", "0", "--dry"],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        # the program is not part of the benchmark's copy
        env={**os.environ, "PYTHONPATH": ROOT},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert KEYS <= set(result)
    assert result["device"]["platform"] == "cpu"
    # safety holds whatever the load on this machine; how many of the
    # payloads a loaded CPU commits inside the drain is not this test's
    assert result["correct"] is True
    assert 0 <= result["failed"] <= result["attempted"]
    assert result["attempted"] == 3000  # 3 s at upstream's 1,000 tx/s
    assert set(result["metrics"]) == {
        "commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"
    }
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]


def test_run_without_the_program_fails_and_prints_no_result(tmp_path):
    """A directory that holds only ``BENCHMARK.json`` and the files
    under ``paths``: the child cannot import the program."""
    checkout = checkout_with_the_cell(tmp_path)
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0", "--dry"],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert done.returncode != 0
    assert "correct" not in done.stdout


def test_child_without_a_tpu_fails_and_prints_no_result(tmp_path):
    done = subprocess.run(
        [sys.executable, "chipbench/child.py", "--run-dir", str(tmp_path),
         "--config", "chipbench/configs/local4.json", "--seed", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert not os.path.exists(tmp_path / "device.json")
    assert "correct" not in done.stdout
