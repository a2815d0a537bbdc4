"""chip_smoke.py and the device path's loud failures, checked on the CPU.

No committee runs here: the smoke's log reading is held to canned lines,
and the entry points to the refusals they owe a host without a chip.
"""

import json
import os
import subprocess
import sys
import types

import pytest

import chip_smoke
from benchmark.__main__ import _run_failed
from benchmark.local import BASE_PORT, LocalBench, safe_base_port
from benchmark.utils import BenchError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, **env):
    base = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run(
        [sys.executable, *args],
        cwd=cwd,
        env={**base, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, **env},
        capture_output=True,
        text=True,
        timeout=120,
    )


# ---- the smoke itself -------------------------------------------------------


def test_smoke_fails_at_the_device_check_without_importing_jax():
    """On a CPU backend the smoke exits non-zero in seconds, names the
    reason, prints no result line — and its parent never imported jax."""
    proc = _run(
        [
            "-c",
            "import sys, chip_smoke; rc = chip_smoke.main([]); "
            "print('PARENT_JAX', 'jax' in sys.modules); sys.exit(rc)",
        ],
        ROOT,
    )
    assert proc.returncode == 1
    assert "needs a TPU, but jax's default backend is 'cpu'" in proc.stdout
    assert "PARENT_JAX False" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert "== build" not in proc.stdout  # failed before anything else ran


T = "2026-01-01T00:00:0"
WARM = {
    "platform": "tpu", "kind": "TPU v5 lite", "count": 1, "kernel": "pallas",
    "pad_shapes": [128, 256, 1024],
    "warm": {
        s: {"first_call_s": 13.0, "cache_hits": 1, "cache_misses": 0}
        for s in ("128", "256", "1024")
    },
}  # fmt: skip


def _logs(tmp_path, *, nodes=("AAAAAAAA", "BBBBBBBB"), device_sigs=950,
          cpu_sigs=50, misses=0, warm=WARM, extra=""):  # fmt: skip
    """A two-node in-process run's logs/ as the harness leaves them."""
    lines = [
        f"{T}0.100Z [INFO] hotstuff_tpu.node.node Device verifier [tpu] "
        f"warm in 40.1 s: {json.dumps(warm)}",
        f"{T}1.000Z [INFO] hotstuff_tpu.consensus.proposer.AAAAAAAA "
        "Created block 1 (payloads pay1) -> blk1",
    ]
    lines += [
        f"{T}1.{i}00Z [INFO] hotstuff_tpu.consensus.core.{node} "
        "Committed block 1 -> blk1"
        for i, node in enumerate(nodes, start=1)
    ]
    lines += [
        f"{T}2.000Z [INFO] hotstuff_tpu.crypto.async_service Verify service "
        f"stats [tpu#1.1]: dispatches=100 device=100 cpu=0 probe=0 "
        f"device_sigs={device_sigs} cpu_sigs={cpu_sigs} "
        f"deadline_misses={misses} waits=0 depth=2 mesh=0 agg=0 agg_sigs=0 "
        "ewma_ms=5.3 zc=0 fb=0",
        extra,
    ]
    logs = tmp_path / "logs"
    logs.mkdir()
    (logs / "node-0.log").write_text("\n".join(lines) + "\n")
    (logs / "client.log").write_text(
        f"{T}0.900Z [INFO] Start sending transactions\n"
        f"{T}0.900Z [INFO] Transactions rate: 200 tx/s\n"
        f"{T}0.900Z [INFO] Transactions size: 512 B\n"
        f"{T}0.950Z [INFO] Sending sample payload pay1\n"
    )
    return str(logs)


def _judge(logs_dir, exit_code=0, output=""):
    report = chip_smoke.scrape_committee(logs_dir)
    return report, chip_smoke.judge_committee(report, 2, exit_code, output)


def test_committee_judge_passes_a_whole_device_run(tmp_path):
    report, bad = _judge(_logs(tmp_path))
    assert bad == []
    assert report["nodes_committing"] == 2 and report["committed_blocks"] == 1
    assert report["device_share"] == 0.95 and report["safety_ok"]
    assert report["boot"]["kind"] == "TPU v5 lite"
    assert report["e2e_latency_ms"] is not None


@pytest.mark.parametrize(
    "kwargs, exit_code, output, reason",
    [
        (dict(device_sigs=0, cpu_sigs=1000), 0, "", "under 90%"),
        (dict(extra="Traceback (most recent call last):\n  boom"), 0, "",
         "Traceback in"),
        (dict(nodes=("AAAAAAAA",)), 1, "", "1 of 2 nodes committed"),
        (dict(nodes=()), 1, "", "no block committed"),
        (dict(misses=1), 0, "", "deadline misses"),
        (dict(), 0, "pid 7 did not exit on SIGTERM within 30 s: killed",
         "SIGKILL"),
        (dict(warm={**WARM, "kernel": "xla"}), 0, "", "not TPU + Pallas"),
        (dict(warm={**WARM, "warm": {"128": {"cache_hits": 0}}}), 0, "",
         "missed the compile cache"),
    ],
)  # fmt: skip
def test_committee_judge_fails(tmp_path, kwargs, exit_code, output, reason):
    _, bad = _judge(_logs(tmp_path, **kwargs), exit_code, output)
    assert any(reason in b for b in bad), bad
    if exit_code:
        assert any("`benchmark local` exited 1" in b for b in bad)


def test_committee_judge_sees_conflicting_commits(tmp_path):
    logs_dir = _logs(tmp_path)
    with open(os.path.join(logs_dir, "node-0.log"), "a") as f:
        f.write(
            f"{T}1.900Z [INFO] hotstuff_tpu.consensus.core.BBBBBBBB "
            "Committed block 1 -> other\n"
        )
    _, bad = _judge(logs_dir)
    assert any("safety violated" in b for b in bad)


# ---- the wan50 stage's own judgement ----------------------------------------


def _host_stats(second: int, **counters) -> str:
    base = dict(
        elapsed_s=second, ancestor_hits=10, ancestor_misses=0,
        sync_requests=0, wan_frames=0, wan_delay_ms=0.0, wan_base_ms=0.0,
    )  # fmt: skip
    base.update(counters)
    return (
        f"{T}{second}.500Z [INFO] hotstuff_tpu.telemetry.hoststats "
        "Host stats: " + " ".join(f"{k}={v}" for k, v in base.items())
    )


def _judge_wan(tmp_path, lines, placed=2, **kwargs):
    placed = "\n".join(
        f"{T}0.200Z [INFO] hotstuff_tpu.consensus.consensus WAN emulation "
        f"active: region r{i}, position {i} of 2"
        for i in range(placed)
    )
    logs_dir = _logs(tmp_path, extra="\n".join([placed, *lines]), **kwargs)
    report = chip_smoke.scrape_committee(logs_dir)
    report.update(chip_smoke.scrape_wan(logs_dir, 2))
    return report, chip_smoke.judge_wan(report, 2)


def test_wan_judge_passes_frames_held_for_their_links(tmp_path):
    report, bad = _judge_wan(
        tmp_path,
        [
            _host_stats(2, wan_frames=10, wan_delay_ms=700.0, wan_base_ms=1.0),
            _host_stats(7, wan_frames=4000, wan_delay_ms=254000.0,
                        wan_base_ms=252000.0, sync_requests=3),
        ],
        device_sigs=1000, cpu_sigs=0,
    )  # fmt: skip
    assert bad == []
    # the last line's counters, over its frames
    assert (report["wan_frames"], report["wan_delay_ms"],
            report["wan_base_ms"]) == (4000, 63.5, 63.0)  # fmt: skip
    assert report["sync_requests"] == 3 and report["wan_nodes_placed"] == 2
    assert report["check_violations"] == []


@pytest.mark.parametrize(
    "lines, kwargs, reason",
    [
        # held 3% longer than the frames' links say
        ([_host_stats(7, wan_frames=100, wan_delay_ms=6489.0,
                      wan_base_ms=6300.0)], {}, "off by more than 2%"),
        # the emulation was not on: a parent's line, or no spec
        ([_host_stats(7)], {}, "no frame was held"),
        ([], {}, "no frame was held"),
        # one wave served by the CPU
        ([_host_stats(7, wan_frames=100, wan_delay_ms=6300.0,
                      wan_base_ms=6300.0)],
         dict(device_sigs=999, cpu_sigs=1), "verified off the chip"),
        # a node that committed nothing: the benchmark's own checker
        ([_host_stats(7, wan_frames=100, wan_delay_ms=6300.0,
                      wan_base_ms=6300.0)],
         dict(nodes=("AAAAAAAA",)), "chipbench/check.py"),
        # a node the spec did not place
        ([_host_stats(7, wan_frames=100, wan_delay_ms=6300.0,
                      wan_base_ms=6300.0)],
         dict(placed=1), "1 of 2 nodes said where"),
    ],
    ids=["held-too-long", "counters-zero", "no-line", "cpu-wave", "checker",
         "unplaced"],
)  # fmt: skip
def test_wan_judge_fails(tmp_path, lines, kwargs, reason):
    kwargs = {"device_sigs": 1000, "cpu_sigs": 0, **kwargs}
    _, bad = _judge_wan(tmp_path, lines, **kwargs)
    assert any(reason in b for b in bad), bad


def test_log_excerpt_prints_the_traceback(tmp_path):
    path = tmp_path / "node-0.log"
    path.write_text("a\nb\nTraceback (most recent call last):\n  File x\nErr\n")
    assert chip_smoke.log_excerpt(str(path)).startswith("Traceback")
    path.write_text("\n".join(str(i) for i in range(100)))
    assert chip_smoke.log_excerpt(str(path)).splitlines()[0] == "60"


# ---- one compile-cache rule -------------------------------------------------


def test_compile_cache_rule(tmp_path):
    """Variable set: jax read it, the code sets nothing.  Unset:
    <checkout>/.jax_cache."""
    code = (
        "import jax, hotstuff_tpu.tpu; "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    unset = _run(["-c", code], str(tmp_path))
    assert unset.stdout.strip() == os.path.join(ROOT, ".jax_cache")
    elsewhere = str(tmp_path / "jc")
    assert (
        _run(
            ["-c", code], str(tmp_path), JAX_COMPILATION_CACHE_DIR=elsewhere
        ).stdout.strip()
        == elsewhere
    )


# ---- one process per chip, and refusals that are loud -----------------------


@pytest.mark.parametrize("verifier", ["tpu", "tpu-sharded", "mesh"])
def test_localbench_refuses_a_device_verifier_per_process(verifier):
    with pytest.raises(BenchError, match="--in-process"):
        LocalBench(nodes=4, verifier=verifier)
    LocalBench(nodes=4, verifier=verifier, in_process=True)


def test_run_many_refuses_a_cpu_backend_and_the_harness_says_so(tmp_path):
    """`node run-many --verifier tpu` exits 1 where jax's backend is not
    a TPU, and `benchmark local` exits non-zero on the dead process."""
    proc = _run(
        ["-m", "benchmark", "local", "--in-process", "--verifier", "tpu",
         "--nodes", "4", "--rate", "100", "--duration", "5"],
        str(tmp_path),
    )  # fmt: skip
    assert proc.returncode == 1
    assert (
        "process died during the run (exit 1): -m hotstuff_tpu.node -vv "
        "run-many" in proc.stdout
    )
    assert "nothing was committed" in proc.stdout
    node_log = (tmp_path / "logs" / "node-0.log").read_text()
    assert "Cannot boot: --verifier tpu needs a TPU" in node_log
    assert not (tmp_path / "results").exists()


def test_harness_exit_status_follows_the_run():
    ok = types.SimpleNamespace(has_window=lambda: True)
    empty = types.SimpleNamespace(has_window=lambda: False)
    whole = types.SimpleNamespace(died=[])
    lost = types.SimpleNamespace(died=[("-m hotstuff_tpu.node run", -9)])
    assert not _run_failed(whole, ok)
    assert _run_failed(whole, empty)  # nothing was committed
    assert _run_failed(lost, ok)  # a process was lost


def test_node_ports_stay_out_of_the_ephemeral_range(tmp_path):
    f = tmp_path / "range"
    f.write_text("32768\t60999\n")
    assert safe_base_port(str(f)) == BASE_PORT
    f.write_text("16000\t65535\n")  # the v5e host's
    base = safe_base_port(str(f))
    assert base + 3_000 + 1_000 <= 16_000 and base >= 1_024
    f.write_text("1024\t65535\n")
    with pytest.raises(BenchError, match="ephemeral"):
        safe_base_port(str(f))
    assert safe_base_port(str(tmp_path / "absent")) == BASE_PORT
