"""The ``wan50`` deployment's benchmark files (ISSUE 32) against
``BENCHMARK.json`` and against ``colo64.nodedup``'s, the two readers of
``readers/wanstats.py`` on ``Host stats:`` lines as the program prints
them, and the new cell end to end on the CPU (``--dry``: the CPU
verifier inline, so no wave and no device number; the link delays are
injected all the same, so it guards the spec, the placement and the
counters as well as the files, the generator and the checker)."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.readers import hoststats, wanstats
from hotstuff_tpu.network.wan import (
    DEFAULT_JITTER_PCT,
    DEFAULT_MATRIX,
    DEFAULT_REGIONS,
    INTRA_REGION_MS,
    mean_link_ms,
)

from . import test_nodedup_cell
from .test_manifest import BENCH, ROOT, load
from .test_nodedup_cell import entry

CELL = "wan50.low"
CONFIG = "wan50"
NEW_METRICS = ("network.wan_delay_ms", "consensus.sync_requests")
#: the rehearsal's node ports: ``test_nodedup_cell.py`` rehearses its
#: committee on 21,000 up, on another worker at the same time
DRY_BASE_PORT = 22_000


def test_the_cell_is_the_issues():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "low-wan50", 1
    )
    traffic = load("traffic", cell["traffic"] + ".json")
    assert traffic["name"] == cell["traffic"]
    assert (traffic["payload_bytes"], traffic["ramp_s"],
            traffic["drain_cap_s"]) == (512, 3, 30)
    # a share of the knee, rounded down to a multiple of 10
    assert traffic["rate_tx_s"] % 10 == 0 and traffic["rate_tx_s"] >= 10


def test_the_configuration_is_the_sources_committee_under_its_own_spec():
    base = load("configs", "colo64.nodedup.json")
    config = load("configs", CONFIG + ".json")
    assert config["name"] == CONFIG
    # the source's size, for once not cut
    assert (config["nodes"], config["faults"], config["guarantees"]["quorum"]) == (
        50, 0, 33
    )
    assert config["env"] == {
        **base["env"], "HOTSTUFF_WAN_SPEC": "chipbench/configs/wan50.json",
    }
    assert set(config["env"]) == {
        "HOTSTUFF_FORCE_DEVICE_ROUTE", "HOTSTUFF_NO_CLAIM_DEDUP",
        "HOTSTUFF_WAN_SPEC",
    }
    same = ("scheme", "payload_bytes", "timeout_delay_ms",
            "sync_retry_delay_ms", "transport", "verifier", "chips")
    assert {k: config[k] for k in same} == {k: base[k] for k in same}
    assert config["transport"] == "asyncio"  # the emulation's senders
    assert config["reduced"] == ["hosts", "links", "input_rate",
                                 "mempool_offload"]
    assert not {"nodes", "verify_fanout"} & set(config["reduced"])
    assert {"nodes", "verify_fanout"} <= set(config["not_reduced"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert {"matrix_one_way_ms", "jitter_pct", "placement", "density",
            "env.HOTSTUFF_FORCE_DEVICE_ROUTE"} <= set(config["assumed"])
    assert set(config["guarantees"]) == set(base["guarantees"]) | {
        "injected_delay"
    }
    assert "wan_delay_ms / wan_frames" in config["guarantees"]["injected_delay"]
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(set(sources)) == len(sources)
    assert "bench-0-50-100000-512.txt" in entry("configs", CONFIG)["source"]


def test_the_configuration_file_is_the_spec_the_program_is_pointed_at():
    config = load("configs", CONFIG + ".json")
    assert os.path.samefile(
        os.path.join(ROOT, config["env"]["HOTSTUFF_WAN_SPEC"]),
        os.path.join(ROOT, "chipbench", "configs", CONFIG + ".json"),
    )
    # the program's own regions and delays, stated where a reader looks
    assert config["regions"] == list(DEFAULT_REGIONS)
    assert config["matrix_one_way_ms"] == {
        f"{a}|{b}": ms for (a, b), ms in DEFAULT_MATRIX.items()
    }
    assert config["intra_region_ms"] == INTRA_REGION_MS
    assert config["jitter_pct"] == DEFAULT_JITTER_PCT
    # ten nodes a region: every directed link alike, 63.36 ms
    assert mean_link_ms(config, config["nodes"]) == pytest.approx(
        (9 * 0.5 + 10 * 2 * 775 / 5) / 49
    )


def test_the_cell_reports_what_nodedup_low_reports_and_the_two_counters():
    # by name and by membership: a later cell is appended to the same
    # lists and a later metric to ``per_layer``, and neither may fail this
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        cells = metric.get("workloads")
        if cells is not None and "colo64.nodedup.low" in cells:
            assert CELL in cells, metric["name"]
    # the three fan-out metrics report wherever the committee runs the
    # ed25519 verify service, so here too (``own_verification``)
    for name in test_nodedup_cell.NEW_METRICS:
        assert test_nodedup_cell.SERVICE_CELLS <= set(
            entry("per_layer", name)["workloads"]
        )
    expected = {
        "network.wan_delay_ms": ("ms", "network", "commit_latency_p50_ms"),
        "consensus.sync_requests": (
            "count", "consensus", "commit_latency_p95_ms"
        ),
    }
    assert set(expected) == set(NEW_METRICS)
    for name, (unit, layer, moves) in expected.items():
        metric = entry("per_layer", name)
        # the two counters exist only under a WAN spec: a second WAN
        # cell is the benchmark PR's that adds it
        assert metric["workloads"] == [CELL]
        assert (metric["unit"], metric["layer"], metric["moves"]) == (
            unit, layer, moves
        )
        assert (metric["better"], metric["source"]) == (
            "lower", "program_counter"
        )
        assert load("layers", name + ".json")["reader"].startswith("wanstats:")


HEAD = (
    "hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=1.000 "
    "cpu_user_s=1.000 cpu_sys_s=0.100 lag_samples=9 lag_mean_ms=2.000 "
    "lag_max_ms=7.000 gc2=0 gc2_s=0.0000 store_appends=100 store_records=700 "
    "ancestor_hits=5 ancestor_misses=2"
)
COUNTERS = [
    (0, 0, 0.0),
    (4, 1_000, 63_000.0),
    (9, 21_000, 1_343_000.0),
    (99, 99_999, 9_999_999.0),
]
LOG = "".join(
    f"2026-10-02T12:00:{10 * i:02d}.000Z [INFO] {HEAD} sync_requests={asks} "
    f"wan_frames={frames} wan_delay_ms={held} wan_base_ms={held}\n"
    for i, (asks, frames, held) in enumerate(COUNTERS)
)
#: what a parent commit prints: the line without the new counters
PARENT = "\n".join(line.split(" sync_requests=")[0] for line in LOG.splitlines())
#: no spec set: the senders hold nothing and the counters stand at 0
NO_SPEC = "\n".join(
    line.split(" sync_requests=")[0]
    + " sync_requests=0 wan_frames=0 wan_delay_ms=0.0 wan_base_ms=0.0"
    for line in LOG.splitlines()
)


class FakeRun:
    """What the readers touch of a ``reduce.Run``."""

    def __init__(self, text: str, after_first_s: float, seconds: float):
        self._host_stats = hoststats.lines_of(text)
        first = hoststats.lines_of(LOG)[0][0]
        self.t0 = first + after_first_s
        self.t1 = self.t0 + seconds


@pytest.mark.parametrize(
    "text, after_first_s, seconds, expected",
    [
        # the line of :20 less the line of :00, and less the line of :10
        (LOG, 5.0, 20.0, (1_343_000.0 / 21_000, 9.0)),
        (LOG, 12.0, 10.0, (1_280_000.0 / 20_000, 5.0)),
        # no frame held in the window: no delay to speak of, no request
        (NO_SPEC, 5.0, 20.0, (None, 0.0)),
        # a parent commit's line has none of the counters
        (PARENT, 5.0, 20.0, (None, None)),
        ("", 5.0, 20.0, (None, None)),
        (LOG.splitlines()[0] + "\n", 5.0, 20.0, (None, None)),
        (LOG, 12.0, 5.0, (None, None)),
    ],
    ids=["two-lines-apart", "one-line-apart", "no-spec", "parent", "empty",
         "one-line", "no-line-in-window"],
)  # fmt: skip
def test_wanstats_readers(text, after_first_s, seconds, expected):
    run = FakeRun(text, after_first_s, seconds)
    got = (wanstats.delay_ms(run), wanstats.sync_requests(run))
    assert got == pytest.approx(expected)


def test_dry_run_of_the_new_cell(tmp_path):
    """50 nodes on this machine's CPU for a few seconds at the cell's
    own rate, the configuration's own file as the spec: the files load,
    every node is placed, the committee commits under the delays, the
    guarantees hold and the line keeps to the contract."""
    checkout = test_nodedup_cell.checkout_on_ports_of_its_own(tmp_path)
    (tmp_path / "benchmark" / "local.py").write_text(
        f"def safe_base_port():\n    return {DRY_BASE_PORT}\n"
    )
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "2147483932", "--seconds", "9", "--trace", "0", "--dry"],
        cwd=checkout, capture_output=True, text=True, timeout=400,
        # the program is not part of the benchmark's copy
        env={**os.environ, "PYTHONPATH": ROOT},
    )  # fmt: skip
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    detail = json.loads(done.stdout.strip().splitlines()[-2])
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, detail["why_not_correct"]
    rate = load("traffic", "low-wan50.json")["rate_tx_s"]
    assert result["attempted"] == 9 * rate
    assert 0 <= result["failed"] <= result["attempted"]
    assert set(result["metrics"]) == {
        "commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"
    }
    every = detail["every_metric"]
    assert every["consensus.view_changes"] == 0
    # the delays were injected: the mean over the links the frames took
    # lies at the matrix's (63.36 ms over all links alike), and a round
    # is made of them
    config = load("configs", CONFIG + ".json")
    assert every["network.wan_delay_ms"] == pytest.approx(
        mean_link_ms(config, config["nodes"]), rel=0.05
    )
    assert every["consensus.round_ms"] > 125.0
    assert every["consensus.sync_requests"] >= 0
    log_path = os.path.join(
        checkout, "chiprun_out", "chipbench", CELL, "node.log"
    )
    with open(log_path) as f:
        assert f.read().count("WAN emulation active: region ") == 50
