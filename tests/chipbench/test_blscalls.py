"""``agg.adds_per_call`` from ``BLS stats:`` lines: lines that carry
``device_calls=`` (one fold a 64-node quorum, three a 256-node one),
lines from a program without the counter and no lines at all, which read
None and never raise, the line as the program prints it, and the
metric's files against ``BENCHMARK.json``."""

import pytest

from chipbench.readers import blscalls

from .test_bls64_cell import FakeRun, stats_line
from .test_manifest import load
from .test_nodedup_cell import entry

NAME = "agg.adds_per_call"


def lines(start: dict, end: dict) -> list[str]:
    return [stats_line("00:05", **start), stats_line("00:55", **end)]


@pytest.mark.parametrize(
    "start, end, expected",
    [
        ({"device_adds": 430, "device_calls": 10},
         {"device_adds": 4730, "device_calls": 110}, 43.0),
        ({"device_adds": 171, "device_calls": 3},
         {"device_adds": 3591, "device_calls": 63}, 57.0),
        ({"device_adds": 0, "device_calls": 2},
         {"device_adds": 43, "device_calls": 3}, 43.0),
    ],
    ids=["bls64-quorums", "bls256-quorums", "after-the-warm-up"],
)  # fmt: skip
def test_device_adds_over_device_calls_in_the_window(start, end, expected):
    assert blscalls.adds_per_call(FakeRun(lines(start, end))) == (
        pytest.approx(expected)
    )


@pytest.mark.parametrize(
    "run_lines",
    [
        lines({"device_adds": 43}, {"device_adds": 430}),
        lines({"device_adds": 43, "device_calls": 1},
              {"device_adds": 43, "device_calls": 1}),
        lines({}, {})[:1],
        [],
    ],
    ids=["a-parents-line", "no-calls", "one-line", "no-line"],
)  # fmt: skip
def test_nothing_to_read_is_none_and_never_raises(run_lines):
    assert blscalls.adds_per_call(FakeRun(run_lines)) is None


def test_the_line_as_the_program_prints_it():
    from hotstuff_tpu.telemetry.blsstats import BlsCounts

    counts = BlsCounts()
    first = counts.line()
    counts.add("device_adds", 171)
    counts.add("device_calls", 3)
    prefix = "2026-01-01T00:{}.000Z [INFO] hotstuff_tpu.telemetry.hoststats BLS stats: "
    run = FakeRun([prefix.format("00:05") + first,
                   prefix.format("00:55") + counts.line()])  # fmt: skip
    assert blscalls.adds_per_call(run) == pytest.approx(57.0)


def test_the_metric_is_in_the_manifest_for_both_bls_cells():
    metric = entry("per_layer", NAME)
    assert metric["workloads"] == ["bls64.low", "bls256.low"]
    assert (metric["unit"], metric["better"], metric["source"]) == (
        "adds/call", "higher", "program_counter"
    )
    assert (metric["layer"], metric["moves"]) == (
        "BLS aggregation", "commit_latency_p50_ms"
    )
    assert load("layers", NAME + ".json")["reader"] == "blscalls:adds_per_call"
