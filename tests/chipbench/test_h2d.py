"""``verify.h2d_per_call`` from ``Verify service stats`` lines as the
program prints them since ISSUE 38 (``... chunks=N h2d=N calls=N``),
numbers small enough to work out by hand, and the metric's files
against ``BENCHMARK.json``."""

import pytest

from chipbench.readers import h2d

from .test_manifest import load
from .test_nodedup_cell import FakeRun, entry, stats

NAME = "verify.h2d_per_call"


@pytest.mark.parametrize(
    "lines, expected",
    [
        # one buffer a call; the warm-up's calls and the four tables of
        # the boot's rebuild lie before the window and cancel
        (
            [
                stats("00:05", "tpu#7.1", device=30, chunks=50, h2d=61,
                      calls=57),
                stats("00:55", "tpu#7.1", device=330, chunks=550, h2d=561,
                      calls=557),
            ],
            1.0,
        ),
        # what the code before it would have counted: five arrays a call
        (
            [
                stats("00:05", "tpu#7.1", device=10, chunks=10, h2d=50,
                      calls=20),
                stats("00:55", "tpu#7.1", device=760, chunks=760, h2d=3_800,
                      calls=1_520),
            ],
            5.0,
        ),
        # a stranger's key inside the window: one rebuild, four arrays
        (
            [
                stats("00:05", "tpu#7.1", device=10, chunks=10, h2d=17,
                      calls=13),
                stats("00:55", "tpu#7.1", device=110, chunks=110, h2d=121,
                      calls=113),
            ],
            104 / 100,
        ),
        # no line before the window's start: the first counts from zero
        (
            [stats("00:55", "tpu#7.1", device=20, chunks=20, h2d=27, calls=23)],
            27 / 20,
        ),
        # two services in the log: their counters add up
        (
            [
                stats("00:05", "tpu#7.1", chunks=10, h2d=10, calls=10),
                stats("00:05", "tpu#7.2", chunks=5, h2d=5, calls=5),
                stats("00:55", "tpu#7.1", chunks=110, h2d=110, calls=110),
                stats("00:55", "tpu#7.2", chunks=25, h2d=65, calls=25),
            ],
            (100 + 60) / (100 + 20),
        ),
    ],
    ids=["one-buffer", "five-arrays", "a-rebuild", "first-line", "two-services"],
)
def test_h2d_over_chunks_of_the_window(lines, expected):
    assert h2d.h2d_per_call(FakeRun(lines)) == pytest.approx(expected)


@pytest.mark.parametrize(
    "lines",
    [
        # a parent commit's line ends at chunks=
        [
            stats("00:05", "tpu#7.1", device=10, chunks=10),
            stats("00:55", "tpu#7.1", device=760, chunks=760),
        ],
        # no backend call in the window
        [
            stats("00:05", "tpu#7.1", chunks=10, h2d=17, calls=13),
            stats("00:55", "tpu#7.1", chunks=10, h2d=17, calls=13),
        ],
        # a line older than the fan-out counters
        [stats("00:55", "tpu#7.1", device=5)],
        [],
    ],
    ids=["parent", "no-call", "no-chunks", "no-lines"],
)
def test_nothing_to_read_is_none_and_never_raises(lines):
    assert h2d.h2d_per_call(FakeRun(lines)) is None


def test_the_metric_is_in_the_manifest_for_every_cell_with_the_service():
    metric = entry("per_layer", NAME)
    assert {"colo64.low", "colo64.nodedup.low", "wan50.low"} <= set(
        metric["workloads"]
    )
    assert (metric["unit"], metric["better"], metric["source"]) == (
        "arrays/call", "lower", "program_counter"
    )
    assert (metric["layer"], metric["moves"]) == (
        "device verifier", "commit_latency_p50_ms"
    )
    assert load("layers", NAME + ".json")["reader"] == "h2d:h2d_per_call"


def test_the_program_prints_the_counter_the_reader_takes(caplog):
    """The stats line of this tree, as the service formats it, through
    the benchmark's own parser."""
    import logging

    from chipbench.logs import CommitteeLog
    from hotstuff_tpu.crypto.async_service import AsyncVerifyService

    class Host:
        async_kind = "tpu"

        def device_counters(self):
            return 23, 19

    name = "hotstuff_tpu.crypto.async_service"
    with caplog.at_level(logging.INFO, logger=name):
        service = AsyncVerifyService(Host(), device=True)
        service.chunks = 19
        service._log_stats()
    log = CommitteeLog()
    log.feed(f"2026-01-01T00:00:55.000Z [INFO] {name} {caplog.messages[-1]}")
    (_, _, counters), = log.stats
    assert (counters["chunks"], counters["h2d"], counters["calls"]) == (19, 23, 19)
