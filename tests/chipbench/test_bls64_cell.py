"""The ``bls64`` deployment's benchmark files against ``BENCHMARK.json``
and against ``colo64``'s, the BLS readers on lines and events as the
program prints and traces them, the parts of the program the
configuration ``needs``, and the cell end to end on the CPU (``--dry``:
the CPU verifier, so the host's add and no device number: it guards the
files, the boot once a process, the log lines and the reference)."""

import json
import logging
import os
import subprocess
import sys

import pytest

from chipbench.child import missing_need
from chipbench.readers import bls

from .test_manifest import BENCH, ROOT, load
from .test_nodedup_cell import checkout_on_ports_of_its_own, entry

CELL, CONFIG, TRAFFIC = "bls64.low", "bls64", "low-bls64"
NEW_METRICS = {
    "bls.sign_ms_per_round": ("ms", "lower", "commit_latency_p50_ms"),
    "agg.device_ms_per_round": ("ms", "lower", "commit_latency_p50_ms"),
    "kernel.g1_add_us": ("us", "lower", "commit_latency_p50_ms"),
    "bls.pairings_per_round": ("count", "lower", "commit_latency_p50_ms"),
    "bls.compact_qc_share": ("%", "higher", "commit_latency_p95_ms"),
    "bls.reference_agree_share": ("%", "higher", "commit_latency_p50_ms"),
}
#: what reads the ed25519 verify service, its device verifier and kernel,
#: or the WAN: nothing there in a BLS committee on loopback links
NOT_REPORTED = {
    "kernel.wave_us", "kernel.waves_per_s", "verifier.exe_load_share",
    "idle.wave_in_flight_share", "network.wan_delay_ms",
    "consensus.sync_requests",
}
#: the rehearsal's node ports, clear of the other rehearsals' (21,000 to
#: 24,000 and 64 above each) and of the test workers' (26,000 on)
DRY_BASE_PORT = 25_000


def test_the_cell_is_the_issues():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1
    )
    traffic = load("traffic", TRAFFIC + ".json")
    assert traffic["name"] == TRAFFIC
    assert (traffic["payload_bytes"], traffic["ramp_s"],
            traffic["drain_cap_s"]) == (512, 3, 30)
    assert traffic["rate_tx_s"] % 10 == 0 and traffic["rate_tx_s"] >= 10


def test_the_configuration_is_colo64_with_the_scheme_changed():
    base = load("configs", "colo64.json")
    config = load("configs", CONFIG + ".json")
    assert config["name"] == CONFIG and config["scheme"] == "bls"
    assert config["env"] == {}  # claim dedup on, nothing pinned
    same = ("nodes", "faults", "payload_bytes", "timeout_delay_ms",
            "sync_retry_delay_ms", "transport", "verifier", "chips")
    assert {k: config[k] for k in same} == {k: base[k] for k in same}
    assert config["reduced"] == [
        "nodes", "hosts", "links", "verify_fanout", "input_rate"
    ]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "256 -> 64" in config["reduced_why"]["nodes"]
    assert {"timeout_delay_ms", "payload_bytes", "aggregation", "density",
            "device_running_sum"} <= set(config["assumed"])
    assert "not t-of-n" in config["assumed"]["aggregation"]
    assert config["guarantees"] == {
        **base["guarantees"],
        "compact_certificates": config["guarantees"]["compact_certificates"],
        "aggregate_reference": config["guarantees"]["aggregate_reference"],
    }
    assert "config 5" in entry("configs", CONFIG)["source"]


def test_it_needs_the_boot_once_a_process(monkeypatch):
    """The two parts a program without them cannot boot 64 BLS nodes
    without: named, present here, and missed where they are absent."""
    needs = load("configs", CONFIG + ".json")["needs"]
    assert needs == [
        "hotstuff_tpu.crypto.bls.service:BlsVerifier.warmup",
        "hotstuff_tpu.crypto.bls.service:check_possession",
    ]
    assert missing_need(needs) is None
    from hotstuff_tpu.crypto.bls import service

    monkeypatch.delattr(service, "check_possession")
    assert missing_need(needs) == needs[1]
    monkeypatch.delattr(service.BlsVerifier, "warmup")
    assert missing_need(needs) == needs[0]


def test_the_cell_reports_what_a_bls_committee_has_to_read():
    reported = {
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
        if CELL in m.get("workloads", [CELL])
    }
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        name, cells = metric["name"], metric.get("workloads")
        if cells is None or "colo64.low" not in cells:
            continue
        ed25519_only = name in NOT_REPORTED or name.startswith("verify.")
        assert (name in reported) != ed25519_only, name
    assert {"setup_s", "verifier.warmup_s", "verifier.cache_hits",
            "setup.boot_s"} <= reported
    for name, (unit, better, moves) in NEW_METRICS.items():
        metric = entry("per_layer", name)
        assert (metric["unit"], metric["better"], metric["moves"]) == (
            unit, better, moves
        )
        assert metric["layer"] == "BLS aggregation"
        assert metric["workloads"] == [CELL]
        assert load("layers", name + ".json")["reader"].startswith("bls:")


def test_the_reference_is_a_copy_of_the_programs():
    with open(os.path.join(ROOT, "hotstuff_tpu", "crypto", "bls_g1_ref.py")) as f:
        program = f.read()
    with open(os.path.join(ROOT, "chipbench", "reference", "bls_g1_ref.py")) as f:
        assert f.read() == program


# ---- the readers -------------------------------------------------------------


class FakeRun:
    """What the BLS log readers take of a ``Run``: the blocks made, the
    window's ends, and the log's lines as the reader keeps them."""

    def __init__(self, lines: list[str], made: list[float] = ()):
        from chipbench.logs import CommitteeLog

        self.log = CommitteeLog()
        for i, at in enumerate(made):
            self.log.created[f"b{i}"] = (at, "n", i, [])
        self.t0, self.t1 = 1_767_225_610.0, 1_767_225_660.0  # :10 to 01:00
        self._bls_log = bls.log_lines("\n".join(lines))


def stats_line(second: str, **counters) -> str:
    base = dict.fromkeys(
        ("signs", "device_adds", "host_adds", "snapshots", "qcs",
         "compact_qcs", "agg_verifies", "agg_failures", "pairings"), 0
    )
    base.update(counters)
    return (
        f"2026-01-01T00:{second}.000Z [INFO] hotstuff_tpu.telemetry.hoststats "
        "BLS stats: " + " ".join(f"{k}={v}" for k, v in base.items())
    )


def test_the_counter_readers():
    lines = [
        stats_line("00:05", qcs=10, compact_qcs=10, pairings=40),
        stats_line("00:55", qcs=90, compact_qcs=90, pairings=360),
    ]
    # 80 blocks made between the two lines, some outside them
    made = [1_767_225_604.0] + [1_767_225_606.0 + k * 0.6 for k in range(80)]
    run = FakeRun(lines, made)
    assert bls.pairings_per_round(run) == pytest.approx(320 / 80)
    assert bls.compact_qc_share(run) == 100.0
    failed = FakeRun([
        lines[0], stats_line("00:55", qcs=90, compact_qcs=90, agg_failures=4),
    ], made)  # fmt: skip
    assert bls.compact_qc_share(failed) == pytest.approx(100 * 76 / 80)
    # a program without the line (a parent, an ed25519 committee)
    assert bls.compact_qc_share(FakeRun([], made)) is None
    assert bls.pairings_per_round(FakeRun([], made)) is None


def test_the_reference_reader_on_the_line_a_qc_maker_logs(caplog):
    """A compact QC made by the program and logged as it logs it agrees
    with the benchmark's reference; a signature or a bitmap bit that
    does not belong to it does not."""
    from hotstuff_tpu.consensus.aggregator import QCMaker
    from hotstuff_tpu.consensus.config import Committee
    from hotstuff_tpu.consensus.messages import Vote
    from hotstuff_tpu.crypto import Digest, PublicKey, Signature
    from hotstuff_tpu.crypto.bls import BlsSecretKey, prove_possession
    from hotstuff_tpu.crypto.scheme import make_cpu_verifier

    sks = [BlsSecretKey(0xB15 + i) for i in range(4)]
    by_pk = {PublicKey(sk.public_key().to_bytes()): sk for sk in sks}
    com = Committee.new(
        [(pk, 1, ("127.0.0.1", 7200 + i)) for i, pk in enumerate(by_pk)],
        scheme="bls",
        pops={pk: prove_possession(sk).to_bytes() for pk, sk in by_pk.items()},
    )
    maker, block = QCMaker(), Digest.of(b"reader block")
    caplog.set_level(logging.INFO, logger="hotstuff_tpu.consensus.aggregator")
    for pk in com.sorted_keys()[:3]:
        vote = Vote(hash=block, round=12, author=pk)
        vote.signature = Signature(
            by_pk[pk].sign(vote.digest().to_bytes()).to_bytes()
        )
        qc = maker.append(vote, com, make_cpu_verifier("bls"), sig_verified=True)
    assert qc is not None and qc.is_compact
    (message,) = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("Compact QC")
    ]
    line = (
        "2026-01-01T00:00:30.000Z [INFO] hotstuff_tpu.consensus.aggregator "
        + message
    )
    _, ((_, rnd, bitmap, agg, sigs),) = bls.log_lines(line)
    assert (rnd, agg, len(sigs)) == (12, qc.agg_sig.to_bytes().hex(), 3)
    assert bls.agree(bitmap, agg, sigs)
    assert not bls.agree(bitmap, agg, sigs[:2] + sigs[:1])
    assert not bls.agree("0f", agg, sigs)
    late = line.replace("00:00:30", "00:01:30")  # after the window
    run = FakeRun([line, line.replace(sigs[2], sigs[0]), late])
    assert bls.reference_agree_share(run) == 50.0
    assert bls.reference_agree_share(FakeRun([late])) is None


def test_the_trace_reduction():
    """Two rounds on the loop thread: self time of the BLS spans a
    round, and a running-sum execution's operations on the device."""
    ms = 1_000_000
    loop = [
        ["proposer.make", 0, 10 * ms, {"round": 1}],
        ["bls.sign", 2 * ms, 6 * ms, {}],
        ["bls.decode", 20 * ms, 1 * ms, {}],
        ["agg.accumulate", 21 * ms, 2 * ms, {}],
        ["proposer.make", 100 * ms, 10 * ms, {"round": 2}],
        ["bls.sign", 101 * ms, 8 * ms, {}],
        ["agg.snapshot", 120 * ms, 3 * ms, {}],
    ]
    modules = [
        ["jit__running_add_impl(7)", 50 * ms, 100_000],
        ["jit__running_add_impl(7)", 60 * ms, 100_000],
        ["jit_verify_compressed(3)", 70 * ms, 100_000],
    ]
    ops = [
        ["fusion.1", 50 * ms, 30_000], ["fusion.2", 50 * ms + 40_000, 50_000],
        ["fusion.1", 60 * ms, 70_000],
        ["verify_compressed.1", 70 * ms, 100_000],
    ]  # fmt: skip
    r = bls.reduce({"loop": loop, "modules": modules, "ops": ops})
    assert r["rounds"] == 2
    assert r["sign_ms_per_round"] == pytest.approx(7.0)
    assert r["device_sum_ms_per_round"] == pytest.approx(3.0)
    assert r["running_adds"] == 2
    assert r["running_add_us"] == pytest.approx(75.0)
    # a parent's trace: no BLS span, nothing to read
    assert bls.reduce({"loop": loop[:1], "modules": [], "ops": []}) is None


def test_dry_run_of_the_new_cell(tmp_path):
    """64 BLS nodes on this machine's CPU for 11 s at the cell's own rate
    (two of the counter lines printed every 5 s inside the window): they
    boot in seconds (proofs and keys once a process), the committee
    commits compact QCs, the reference agrees with every one and the
    guarantees hold."""
    checkout = checkout_on_ports_of_its_own(tmp_path)
    with open(os.path.join(checkout, "benchmark", "local.py"), "w") as f:
        f.write(f"def safe_base_port():\n    return {DRY_BASE_PORT}\n")
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "2147484001", "--seconds", "11", "--trace", "0", "--dry"],
        cwd=checkout, capture_output=True, text=True, timeout=400,
        # the program is not part of the benchmark's copy
        env={**os.environ, "PYTHONPATH": ROOT},
    )  # fmt: skip
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    detail = json.loads(done.stdout.strip().splitlines()[-2])
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, detail["why_not_correct"]
    rate = load("traffic", TRAFFIC + ".json")["rate_tx_s"]
    assert result["attempted"] == 11 * rate
    assert set(result["metrics"]) == {
        "commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"
    }
    every = detail["every_metric"]
    assert every["setup_s"] < 120
    assert every["bls.compact_qc_share"] == 100.0
    assert every["bls.reference_agree_share"] == 100.0
    assert every["bls.pairings_per_round"] > 0
    # untraced, and the CPU verifier warms nothing
    assert every["bls.sign_ms_per_round"] is None
    assert every["verifier.warmup_s"] is None
