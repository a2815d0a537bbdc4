"""``child.py``'s committee writer for either scheme (ISSUE 35): for
ed25519 it writes what it wrote before it learnt BLS, so the accepted
cells' keys, committees and leader rotations do not move; for BLS every
key carries its proof of possession, in its key file and in the
committee, and the program's own check passes."""

import base64
import json
import os

import pytest

import benchmark.local
from chipbench.child import write_committee
from hotstuff_tpu.node.config import read_committee

from .test_manifest import load

HERE = os.path.dirname(os.path.abspath(__file__))
SEED, BASE_PORT = 7, 10_000
with open(os.path.join(HERE, "data", "committee_ed25519_seed7.json")) as f:
    PARENT = json.load(f)


def read_json(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("scheme", ["ed25519", "bls"])
def test_write_committee(scheme, tmp_path, monkeypatch):
    # nothing listens: the port only has to be the fixture's
    monkeypatch.setattr(benchmark.local, "safe_base_port", lambda: BASE_PORT)
    config = {**load("configs", PARENT["config"] + ".json"), "scheme": scheme}
    key_files = write_committee(str(tmp_path), config, SEED)
    nodes = config["nodes"]
    assert key_files == [str(tmp_path / f"node_{i}.json") for i in range(nodes)]
    keys = [read_json(path) for path in key_files]
    committee = read_committee(str(tmp_path / "committee.json"))
    assert committee.scheme == scheme
    by_name = {
        name.encode_base64(): authority
        for name, authority in committee.authorities.items()
    }
    # the generator knows the nodes in the order of the keys
    assert read_json(tmp_path / "nodes.json") == [
        {"name": key["name"][:8], "address": ["127.0.0.1", BASE_PORT + i]}
        for i, key in enumerate(keys)
    ]
    assert [
        (by_name[key["name"]].stake, tuple(by_name[key["name"]].address))
        for key in keys
    ] == [(1, ("127.0.0.1", BASE_PORT + i)) for i in range(nodes)]
    parameters = read_json(tmp_path / "parameters.json")["consensus"]
    assert {k: parameters[k] for k in PARENT["parameters"]} == {
        "timeout_delay": config["timeout_delay_ms"],
        "sync_retry_delay": config["sync_retry_delay_ms"],
    }
    if scheme == "ed25519":
        # name, secret, scheme and nothing else: no ``pop``
        assert keys == PARENT["keys"]
        assert [
            {"name": key["name"], "stake": by_name[key["name"]].stake,
             "port_offset": by_name[key["name"]].address[1] - BASE_PORT}
            for key in keys
        ] == PARENT["authorities"]  # fmt: skip
        assert all(a.pop is None for a in by_name.values())
    else:
        assert all(set(key) == {"name", "secret", "scheme", "pop"} for key in keys)
        assert [by_name[key["name"]].pop for key in keys] == [
            base64.b64decode(key["pop"]) for key in keys
        ]
        # what ``Consensus.spawn`` asks of a BLS committee at boot
        committee.verify_pops()
    assert len({key["name"] for key in keys}) == nodes
    # the same seed, the same committee: a cell's leader rotation is the seed's
    again = tmp_path / "again"
    again.mkdir()
    write_committee(str(again), config, SEED)
    for name in ("committee.json", "nodes.json", "node_0.json"):
        assert (again / name).read_bytes() == (tmp_path / name).read_bytes()
