"""The benchmark takes a fourth cell as new files and list appends
(ISSUE 35): a later PR may add files and entries and may edit no file
that is there, so the accepted tests have to hold with a cell and a
metric they have never heard of, and ``child.py`` has to boot its
committee.

The cell added here, to a copy, is a 4-node BLS committee: the scheme no
cell runs yet (``BASELINE.json`` config 5), which ``child.py`` could not
start before it wrote proofs of possession.  Nothing of it is in the
repository's own ``BENCHMARK.json``: the deployment at its size, its
reference and its cell are a ``model_config`` PR's."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from .test_manifest import ROOT, load
from .test_nodedup_cell import checkout_on_ports_of_its_own

CELL, CONFIG, TRAFFIC = "bls4.low", "bls4", "low-bls4"
METRIC = "consensus.round_ms.bls"
#: the rehearsal's node ports: the other rehearsals have 21,000 and
#: 22,000, and the harness's own base port
DRY_BASE_PORT = 23_000


@pytest.fixture(scope="module")
def checkout(tmp_path_factory) -> str:
    """A copy of the benchmark's files with the cell added the way a
    later PR has to: three new files, and appends to lists of
    ``BENCHMARK.json``.  No file of the copy is otherwise touched."""
    root = tmp_path_factory.mktemp("admits")
    checkout_on_ports_of_its_own(root)
    (root / "benchmark" / "local.py").write_text(
        f"def safe_base_port():\n    return {DRY_BASE_PORT}\n"
    )
    shutil.copytree(
        os.path.join(ROOT, "tests", "chipbench"), root / "tests" / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )  # fmt: skip
    (root / "tests" / "__init__.py").write_text("")

    def write_json(*parts, data) -> None:
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(data, f, indent=1)

    base = load("configs", "colo64.json")
    config = {
        **base,
        "name": CONFIG,
        "source": "tests/chipbench/test_admits_a_cell.py: colo64.json cut to 4 nodes under BLS12-381",
        "nodes": 4,
        "scheme": "bls",
        "env": {},
        "guarantees": {**base["guarantees"], "quorum": 3},
    }  # fmt: skip
    assert set(config) == set(base)
    write_json("chipbench", "configs", f"{CONFIG}.json", data=config)
    write_json(
        "chipbench", "traffic", f"{TRAFFIC}.json",
        data={"name": TRAFFIC, "rate_tx_s": 20, "payload_bytes": 512,
              "ramp_s": 3, "drain_cap_s": 10},
    )  # fmt: skip
    # a metric of its own that reuses a reader that is there
    write_json(
        "chipbench", "layers", f"{METRIC}.json",
        data={"reader": load("layers", "consensus.round_ms.json")["reader"],
              "what": "the round of the BLS committee"},
    )  # fmt: skip

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": CONFIG, "source": config["source"],
        "file": f"chipbench/configs/{CONFIG}.json",
        "reduced": config["reduced"], "why": "a scheme no cell runs yet",
    })  # fmt: skip
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "4 nodes under BLS, 20 tx/s of 512 B, open loop",
    })  # fmt: skip
    for metric in bench["end_to_end"]:
        if metric["name"].startswith("commit_latency_"):
            metric["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "consensus",
        "moves": "commit_latency_p50_ms", "workloads": [CELL],
    })  # fmt: skip
    write_json("BENCHMARK.json", data=bench)
    return str(root)


def test_the_accepted_tests_hold_with_the_cell_added(checkout):
    """The copy's own manifest and cell tests, less their rehearsals
    (minutes each, and run on the repository's files as it is)."""
    files = ["test_manifest.py", "test_nodedup_cell.py", "test_wan50_cell.py"]
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-k", "not test_dry_run_of_the_new_cell",
         *[os.path.join("tests", "chipbench", name) for name in files]],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        # the program is not part of the benchmark's copy
        env={**os.environ, "PYTHONPATH": ROOT},
    )  # fmt: skip
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    # it was the copy's manifest that they read
    assert f"test_cell_files_exist_and_load[{CELL}]" in done.stdout
    assert f"test_metric_file_reader_and_names[{METRIC}]" in done.stdout
    assert " failed" not in done.stdout and " error" not in done.stdout


def test_dry_run_of_the_added_cell(checkout):
    """The harness starts a BLS committee and the cell is correct: 4
    nodes on this machine's CPU for a few seconds at 20 tx/s."""
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "6", "--trace", "0", "--dry"],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": ROOT},
    )  # fmt: skip
    log_path = os.path.join(
        checkout, "chiprun_out", "chipbench", CELL, "node.log"
    )
    log = ""
    if os.path.exists(log_path):
        with open(log_path, errors="replace") as f:
            log = f.read()
    assert done.returncode == 0, done.stderr[-2000:] + log[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    detail = json.loads(done.stdout.strip().splitlines()[-2])
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, detail["why_not_correct"]
    assert (result["attempted"], result["failed"]) == (6 * 20, 0)
    assert set(result["metrics"]) == {
        "commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"
    }
    assert detail["every_metric"][METRIC] > 0
    # the committee the child wrote is a BLS one, proofs and all
    run_dir = os.path.dirname(log_path)
    with open(os.path.join(run_dir, "committee.json")) as f:
        committee = json.load(f)["consensus"]
    assert committee["scheme"] == "bls"
    assert all("pop" in a for a in committee["authorities"].values())
