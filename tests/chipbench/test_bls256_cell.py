"""The ``bls256`` deployment's benchmark files against ``BENCHMARK.json``
and against ``bls64``'s, by membership and never by position: the
committee at its published 256 nodes (``nodes`` not cut), its quorum of
171, the parts of the program it ``needs``, its traffic, and the metrics
it reports, the new ones included, with their readers on lines as the
program prints them."""

from chipbench.child import missing_need

from .test_manifest import BENCH, load
from .test_nodedup_cell import entry

CELL, CONFIG, TRAFFIC = "bls256.low", "bls256", "low-bls256"
#: this PR's metrics: what each reads and moves
NEW_METRICS = {
    "network.conn_opens_per_round": (
        "conns/round", "network", "commit_latency_p50_ms", "connstats:"
    ),
    "setup.keys_s": ("s", "set-up", "setup_s", "boot:"),
    "setup.nodes_s": ("s", "set-up", "setup_s", "boot:"),
}


def test_the_cell_is_the_issues():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1
    )
    traffic = load("traffic", TRAFFIC + ".json")
    assert traffic["name"] == TRAFFIC
    assert (traffic["payload_bytes"], traffic["ramp_s"],
            traffic["drain_cap_s"]) == (512, 3, 30)
    # bls64.low's rate, so that the two cells differ in the committee's
    # size alone, unless the knee's sweep asked for less (and says so)
    rate = traffic["rate_tx_s"]
    assert rate == 90 or (rate in (60, 45) and "sweep" in traffic["what"])
    assert "knee" in traffic["what"]


def test_the_configuration_is_bls64_at_its_published_size():
    base = load("configs", "bls64.json")
    config = load("configs", CONFIG + ".json")
    assert config["name"] == CONFIG and config["scheme"] == "bls"
    assert config["nodes"] == 256 and config["guarantees"]["quorum"] == 171
    assert "nodes" not in config["reduced"]
    assert set(config["reduced"]) == {
        "hosts", "links", "verify_fanout", "input_rate"
    }
    assert set(config["reduced_why"]) == set(config["reduced"])
    same = ("faults", "payload_bytes", "timeout_delay_ms",
            "sync_retry_delay_ms", "transport", "verifier", "chips", "env")
    assert {k: config[k] for k in same} == {k: base[k] for k in same}
    # every guarantee of bls64's, restated for 171 of 256
    assert set(config["guarantees"]) == set(base["guarantees"])
    assert "171 of the 256" in config["guarantees"]["committed"]
    for kept in ("agreement", "once", "liveness", "aggregate_reference"):
        assert config["guarantees"][kept] == base["guarantees"][kept]
    assert set(base["assumed"]) | {"connections"} <= set(config["assumed"])
    assert "20,000" in config["assumed"]["connections"]
    assert "config 5" in entry("configs", CONFIG)["source"]
    assert entry("configs", CONFIG)["source"] != entry("configs", "bls64")["source"]


def test_it_needs_the_parts_this_program_brings(monkeypatch):
    """Every part named resolves here; without the boot's keys phase or
    the connection counter (a parent commit) the cell is refused before
    jax is imported."""
    needs = load("configs", CONFIG + ".json")["needs"]
    assert set(load("configs", "bls64.json")["needs"]) <= set(needs)
    assert missing_need(needs) is None
    from hotstuff_tpu.network import pool
    from hotstuff_tpu.node import main

    monkeypatch.delattr(pool, "CONN_COUNTS")
    assert missing_need(needs) == "hotstuff_tpu.network.pool:CONN_COUNTS"
    monkeypatch.delattr(main, "_check_committee_keys")
    assert missing_need(needs) == "hotstuff_tpu.node.main:_check_committee_keys"


def test_the_cell_reports_what_bls64_low_reports_and_its_own():
    """Every metric whose list holds ``bls64.low`` beside other cells
    holds this cell too; a list that holds ``bls64.low`` alone is held
    so by an accepted test and waits for a benchmark PR."""
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        cells = metric.get("workloads")
        if cells is not None and "bls64.low" in cells and cells != ["bls64.low"]:
            assert CELL in cells, metric["name"]
    for name, (unit, layer, moves, reader) in NEW_METRICS.items():
        metric = entry("per_layer", name)
        assert {"bls64.low", CELL} <= set(metric["workloads"])
        assert (metric["unit"], metric["layer"], metric["moves"]) == (
            unit, layer, moves
        )
        assert load("layers", name + ".json")["reader"].startswith(reader)
