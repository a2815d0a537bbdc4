"""The ``colo64.nodedup`` deployment's benchmark files (ISSUE 28)
against ``BENCHMARK.json`` and against ``colo64``'s, the three fan-out
readers on lines as the program prints them, and the new cell end to
end on the CPU (``--dry``: the CPU verifier inline, so no wave and no
device number: it guards the files, the generator and the checker)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.logs import CommitteeLog
from chipbench.readers import fanout

from .test_manifest import BENCH, ROOT, load

CELL = "colo64.nodedup.low"
CONFIG = "colo64.nodedup"
NEW_METRICS = (
    "verify.evaluated_share", "verify.lane_fill_share",
    "verify.chunks_per_wave",
)
#: the cells whose committee runs the ed25519 verify service, whose
#: stats line the three read: a later cell that runs it joins the lists
SERVICE_CELLS = {"colo64.low", CELL, "wan50.low"}


def entry(kind: str, name: str) -> dict:
    (found,) = [e for e in BENCH[kind] if e["name"] == name]
    return found


def test_the_cell_is_the_issues():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "low-colo64-nodedup", 1
    )
    traffic = load("traffic", cell["traffic"] + ".json")
    assert traffic["name"] == cell["traffic"]
    assert (traffic["payload_bytes"], traffic["ramp_s"],
            traffic["drain_cap_s"]) == (512, 3, 30)
    # a share of the knee, rounded down to a multiple of 5
    assert traffic["rate_tx_s"] % 5 == 0 and traffic["rate_tx_s"] >= 5


def test_the_configuration_is_colo64_with_one_more_env_key():
    base = load("configs", "colo64.json")
    config = load("configs", CONFIG + ".json")
    assert config["name"] == CONFIG
    assert config["env"] == {**base["env"], "HOTSTUFF_NO_CLAIM_DEDUP": "1"}
    same = ("nodes", "faults", "scheme", "payload_bytes", "timeout_delay_ms",
            "sync_retry_delay_ms", "transport", "verifier", "chips")
    assert {k: config[k] for k in same} == {k: base[k] for k in same}
    # the cut colo64 makes and this configuration takes back
    assert "verify_fanout" in base["reduced"]
    assert config["reduced"] == [
        k for k in base["reduced"] if k != "verify_fanout"
    ]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "memo" in config["reduced_why"]["hosts"]
    assert {"env.HOTSTUFF_FORCE_DEVICE_ROUTE", "timeout_delay_ms",
            "density"} <= set(config["assumed"])
    assert config["guarantees"] == {
        **base["guarantees"],
        "own_verification": config["guarantees"]["own_verification"],
    }
    assert "submitted_sigs" in config["guarantees"]["own_verification"]
    assert entry("configs", CONFIG)["source"] != entry("configs", "colo64")[
        "source"
    ]


def test_the_cell_reports_what_colo64_low_reports_and_the_fan_out():
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        cells = metric.get("workloads")
        if cells is not None and "colo64.low" in cells:
            assert CELL in cells, metric["name"]
    for name in NEW_METRICS:
        metric = entry("per_layer", name)
        assert SERVICE_CELLS <= set(metric["workloads"])
        assert metric["moves"] == "commit_latency_p50_ms"
        assert load("layers", name + ".json")["reader"].startswith("fanout:")


class FakeRun:
    """What the fan-out readers take of a ``Run``: the log and the
    window's ends."""

    def __init__(self, lines: list[str]):
        self.log = CommitteeLog()
        self.log.feed("\n".join(lines))
        self.t0, self.t_end = 1_767_225_610.0, 1_767_225_660.0  # :10 to 01:00


def stats(second: str, tag: str, **counters) -> str:
    base = dict(
        dispatches=0, device=0, cpu=0, probe=0, device_sigs=0, cpu_sigs=0,
        deadline_misses=0, waits=0, depth=2, mesh=0, agg=0, agg_sigs=0,
        ewma_ms=8.1, zc=0, fb=0,
    )
    base.update(counters)
    return (
        f"2026-01-01T00:{second}.000Z [INFO] hotstuff_tpu.crypto.async_service "
        f"Verify service stats [{tag}]: "
        + " ".join(f"{k}={v}" for k, v in base.items())
    )


@pytest.mark.parametrize(
    "lines, expected",
    [
        # dedup off: a round is one wave of 2,816 signatures in three
        # chunks of 1,024 and two of 32 votes in the 64-bucket
        (
            [
                stats("00:05", "tpu#7.1", dispatches=30, device=30,
                      device_sigs=28_800, submitted_sigs=28_800,
                      lanes=32_000, chunks=50),
                stats("00:55", "tpu#7.1", dispatches=330, device=330,
                      device_sigs=316_800, submitted_sigs=316_800,
                      lanes=352_000, chunks=550),
            ],
            (100.0, 100.0 * 288_000 / 320_000, 500 / 300),
        ),
        # dedup on: 64 nodes hand in what one evaluation serves
        (
            [
                stats("00:05", "tpu#7.1", dispatches=10, device=10,
                      device_sigs=400, submitted_sigs=9_000, lanes=640,
                      chunks=10),
                stats("00:55", "tpu#7.1", dispatches=760, device=760,
                      device_sigs=30_400, submitted_sigs=684_000,
                      lanes=48_640, chunks=760),
            ],
            (100.0 * 30_000 / 675_000, 100.0 * 30_000 / 48_000, 1.0),
        ),
        # a parent commit's line has none of the three counters
        (
            [
                stats("00:05", "tpu#7.1", dispatches=10, device=10,
                      device_sigs=400),
                stats("00:55", "tpu#7.1", dispatches=760, device=760,
                      device_sigs=30_400),
            ],
            (None, None, None),
        ),
        # nothing printed at all
        ([], (None, None, None)),
    ],
    ids=["nodedup", "dedup", "parent", "no-lines"],
)
def test_fanout_readers(lines, expected):
    run = FakeRun(lines)
    got = (
        fanout.evaluated_share(run), fanout.lane_fill_share(run),
        fanout.chunks_per_wave(run),
    )
    assert got == pytest.approx(expected)


#: the rehearsal's node ports: ``test_dry_run.py`` rehearses its 4-node
#: committee on the harness's own base port, on another worker at the
#: same time, and two committees cannot bind the same ports
DRY_BASE_PORT = 21_000


def checkout_on_ports_of_its_own(tmp_path) -> str:
    """A copy of the benchmark's files, as ``test_dry_run.py`` makes
    one, in which the child finds ``benchmark.local.safe_base_port``
    answering ``DRY_BASE_PORT`` (the child looks in its own checkout
    first, then in the program's on ``PYTHONPATH``)."""
    shutil.copytree(
        os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.mkdir(tmp_path / "benchmark")
    (tmp_path / "benchmark" / "__init__.py").write_text("")
    (tmp_path / "benchmark" / "local.py").write_text(
        f"def safe_base_port():\n    return {DRY_BASE_PORT}\n"
    )
    return str(tmp_path)


def test_dry_run_of_the_new_cell(tmp_path):
    """64 nodes on this machine's CPU for a few seconds at the cell's
    own rate: the files load, the committee commits, the guarantees
    hold and the line keeps to the contract."""
    checkout = checkout_on_ports_of_its_own(tmp_path)
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "2147483928", "--seconds", "4", "--trace", "0", "--dry"],
        cwd=checkout, capture_output=True, text=True, timeout=400,
        # the program is not part of the benchmark's copy
        env={**os.environ, "PYTHONPATH": ROOT},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    detail = json.loads(done.stdout.strip().splitlines()[-2])
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, detail["why_not_correct"]
    rate = load("traffic", "low-colo64-nodedup.json")["rate_tx_s"]
    assert result["attempted"] == 4 * rate
    assert 0 <= result["failed"] <= result["attempted"]
    assert set(result["metrics"]) == {
        "commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"
    }
    # the CPU verifier runs inline: no service line, so no fan-out
    assert all(
        detail["every_metric"][name] is None for name in NEW_METRICS
    )
    assert os.path.isfile(
        os.path.join(checkout, "chiprun_out", "chipbench", CELL, "detail.json")
    )
