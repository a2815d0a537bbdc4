"""``bls.native_sign_share`` from ``BLS stats:`` lines: lines that carry
``native_signs=`` (every signature native, some, none), lines from a
program without the counter and no lines at all, which read None and
never raise, the line as the program prints it, and the metric's files
against ``BENCHMARK.json``."""

import pytest

from chipbench.readers import blssign

from .test_bls64_cell import CELL, FakeRun, stats_line
from .test_manifest import load
from .test_nodedup_cell import entry

NAME = "bls.native_sign_share"


def lines(start: dict, end: dict) -> list[str]:
    return [stats_line("00:05", **start), stats_line("00:55", **end)]


@pytest.mark.parametrize(
    "start, end, expected",
    [
        ({"signs": 100, "native_signs": 100},
         {"signs": 3000, "native_signs": 3000}, 100.0),
        ({"signs": 100, "native_signs": 40},
         {"signs": 3000, "native_signs": 2215}, 100 * 2175 / 2900),
        ({"signs": 100, "native_signs": 0},
         {"signs": 3000, "native_signs": 0}, 0.0),
    ],
    ids=["all-native", "partial", "all-python"],
)  # fmt: skip
def test_native_signs_over_signs_in_the_window(start, end, expected):
    assert blssign.native_sign_share(FakeRun(lines(start, end))) == (
        pytest.approx(expected)
    )


@pytest.mark.parametrize(
    "run_lines",
    [
        lines({"signs": 100}, {"signs": 3000}),
        lines({"signs": 100, "native_signs": 100},
              {"signs": 100, "native_signs": 100}),
        lines({}, {})[:1],
        [],
    ],
    ids=["before-the-counter", "no-signs", "one-line", "no-line"],
)  # fmt: skip
def test_nothing_to_read_is_none_and_never_raises(run_lines):
    assert blssign.native_sign_share(FakeRun(run_lines)) is None


def test_the_line_as_the_program_prints_it():
    from hotstuff_tpu.telemetry.blsstats import BlsCounts

    counts = BlsCounts()
    first = counts.line()
    for _ in range(5):
        counts.add("signs")
    for _ in range(4):
        counts.add("native_signs")
    prefix = "2026-01-01T00:{}.000Z [INFO] hotstuff_tpu.telemetry.hoststats BLS stats: "
    run = FakeRun([prefix.format("00:05") + first,
                   prefix.format("00:55") + counts.line()])  # fmt: skip
    assert blssign.native_sign_share(run) == pytest.approx(80.0)


def test_the_metric_is_in_the_manifest_for_the_bls_cell():
    metric = entry("per_layer", NAME)
    assert metric["workloads"] == [CELL]
    assert (metric["unit"], metric["better"], metric["source"]) == (
        "%", "higher", "program_counter"
    )
    assert (metric["layer"], metric["moves"]) == (
        "BLS aggregation", "commit_latency_p50_ms"
    )
    assert load("layers", NAME + ".json")["reader"] == (
        "blssign:native_sign_share"
    )
