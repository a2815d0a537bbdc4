"""``verifier.exe_load_share`` from ``Device verifier ... warm in``
lines: one whose shapes carry the ``exe`` key the executable store adds
(some loaded, some built), one from before that key, which reads None
and never raises, and the metric's files against ``BENCHMARK.json``."""

import json

import pytest

from chipbench.readers import exestore, verifier

from .test_manifest import load
from .test_nodedup_cell import FakeRun, entry

NAME = "verifier.exe_load_share"
SPLIT = {"trace_s": 0.0, "lower_s": 0.0, "compile_or_load_s": 0.0}


def warm_line(shapes: dict) -> str:
    described = {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "kernel": "pallas", "pad_shapes": [128, 256, 1024], "warm": shapes,
    }
    return (
        "2026-01-01T00:00:02.000Z [INFO] hotstuff_tpu.node.node Device "
        f"verifier [tpu] warm in 1.9 s: {json.dumps(described)}"
    )


LOADED = {**SPLIT, "first_call_s": 0.4, "cache_hits": 1, "cache_misses": 0,
          "exe": "loaded", "exe_ms": 121.5}
BUILT = {**SPLIT, "first_call_s": 15.2, "trace_s": 4.1, "lower_s": 8.9,
         "compile_or_load_s": 0.13, "cache_hits": 1, "cache_misses": 0,
         "exe": "built", "exe_ms": 310.0}
BEFORE = {**SPLIT, "first_call_s": 13.2, "trace_s": 4.1, "lower_s": 8.9,
          "compile_or_load_s": 0.13, "cache_hits": 1, "cache_misses": 0}


@pytest.mark.parametrize(
    "shapes, expected",
    [
        ({"128": LOADED, "256": LOADED, "1024": LOADED}, 100.0),
        ({"128": LOADED, "256": BUILT, "1024": LOADED}, 200 / 3),
        ({"128": BUILT, "256": BUILT, "1024": BUILT}, 0.0),
    ],
    ids=["all-loaded", "one-built", "all-built"],
)
def test_loaded_shapes_over_warmed_shapes(shapes, expected):
    run = FakeRun([warm_line(shapes)])
    assert exestore.exe_load_share(run) == pytest.approx(expected)
    # a loaded shape counts as a hit of the compile cache's directory
    assert verifier.cache_hits(run) == 100.0


@pytest.mark.parametrize(
    "lines",
    [
        [warm_line({"128": BEFORE, "256": BEFORE, "1024": BEFORE})],
        [warm_line({})],
        [],
    ],
    ids=["before-the-store", "no-shapes", "no-line"],
)
def test_nothing_to_read_is_none_and_never_raises(lines):
    assert exestore.exe_load_share(FakeRun(lines)) is None


def test_the_metric_is_in_the_manifest_for_every_cell():
    metric = entry("per_layer", NAME)
    assert set(metric["workloads"]) == {
        "colo64.low", "colo64.nodedup.low", "wan50.low"
    }
    assert (metric["unit"], metric["better"], metric["source"]) == (
        "%", "higher", "program_counter"
    )
    assert (metric["layer"], metric["moves"]) == ("device verifier", "setup_s")
    assert load("layers", NAME + ".json")["reader"] == (
        "exestore:exe_load_share"
    )
