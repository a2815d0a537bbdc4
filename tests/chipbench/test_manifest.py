"""BENCHMARK.json against the files it names: every cell's
configuration, traffic and metric files exist and load, every name and
unit keeps to the driver's characters, every per-layer metric moves an
end-to-end metric that each of its cells reports."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for metric in BENCH["end_to_end"]:
        assert set(metric) <= {
            "name", "unit", "better", "bound", "source", "workloads"
        }
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in BENCH["per_layer"]:
        assert set(metric) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads"
        }
        assert "bound" not in metric
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


def test_no_cell_asks_for_four_chips():
    assert [w["chips"] for w in BENCH["workloads"]] == [1] * len(CELLS)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=CELLS)
def test_cell_files_exist_and_load(cell):
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
    assert "\n" not in cell["why"] and "\t" not in cell["why"]
    entry = [c for c in BENCH["configs"] if c["name"] == cell["config"]]
    assert len(entry) == 1
    assert entry[0]["file"] == f"chipbench/configs/{cell['config']}.json"
    config = load("configs", cell["config"] + ".json")
    assert config["source"] == entry[0]["source"] and len(config["source"]) <= 200
    assert config["reduced"] == entry[0]["reduced"]
    assert all(NAME.match(key) for key in config["reduced"])
    nodes, quorum = config["nodes"], config["guarantees"]["quorum"]
    assert quorum == 2 * ((nodes - 1) // 3) + 1
    assert config["chips"] == cell["chips"]
    traffic = load("traffic", cell["traffic"] + ".json")
    assert traffic["rate_tx_s"] > 0
    assert traffic["payload_bytes"] == config["payload_bytes"]
    reported = [m for m in BENCH["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_every_config_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_file_reader_and_names(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock"
    )
    assert set(cells_of(metric)) <= set(CELLS)
    # the metric's own file names its reader and what it reads; unit,
    # layer, moves and cells are BENCHMARK.json's alone
    own = load("layers", metric["name"] + ".json")
    assert set(own) == {"reader", "what"}
    module, function = own["reader"].split(":")
    reader = getattr(
        importlib.import_module(f"chipbench.readers.{module}"), function
    )
    assert callable(reader)
    if "moves" in metric:
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
        assert len(moved) == 1
        assert set(cells_of(metric)) <= set(cells_of(moved[0]))


def test_files_under_paths_keep_to_the_allowed_characters():
    for base in BENCH["paths"]:
        assert PATH.match(base) and not base.startswith("/")
        for folder, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PATH.match(rel), rel
    for kind in ("traffic", "layers", "configs"):
        for name in os.listdir(os.path.join(ROOT, "chipbench", kind)):
            assert name.endswith(".json"), name


#: what a configuration may name under ``needs``: a module of the
#: program and an attribute of it, or of a class of it
NEED = re.compile(
    r"^[A-Za-z_]\w*(\.[A-Za-z_]\w*)*:[A-Za-z_]\w*(\.[A-Za-z_]\w*)*$", re.ASCII
)


@pytest.mark.parametrize(
    "entry", BENCH["configs"], ids=[c["name"] for c in BENCH["configs"]]
)
def test_what_a_configuration_needs_is_named_and_is_there(entry):
    """``needs`` is optional: a list of ``"package.module:attribute"``
    (``Class.method`` for a method), each a part of the program that ``child.py`` looks up before it
    opens the chip.  A cell of this tree's manifest names only what this
    tree's program has."""
    from chipbench.child import missing_need

    needs = load("configs", entry["name"] + ".json").get("needs", [])
    assert isinstance(needs, list) and len(needs) <= 16
    for need in needs:
        assert isinstance(need, str) and NEED.match(need), need
    assert missing_need(needs) is None
