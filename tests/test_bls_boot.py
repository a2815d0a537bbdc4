"""A BLS committee boots once a process, not once a node: the proofs of
possession are checked once for each exact ``(key, proof)`` pair, the
committee's keys are decoded once for every verifier of the process, the
device's G1 programs are warmed once, and a QC maker's running sum
follows the verifier its node was given."""

from __future__ import annotations

import asyncio
import json
import logging
import os
import subprocess
import sys

import pytest

from hotstuff_tpu.consensus import Committee, Consensus, Parameters
from hotstuff_tpu.consensus.aggregator import _SigAccumulator
from hotstuff_tpu.consensus.config import InvalidCommittee
from hotstuff_tpu.crypto import PublicKey
from hotstuff_tpu.crypto.bls import BlsSecretKey, prove_possession
from hotstuff_tpu.crypto.bls import service
from hotstuff_tpu.crypto.bls.service import (
    BlsSigningService,
    BlsVerifier,
    check_possession,
    possession_holds,
)

from chipbench.logs import CommitteeLog

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SECRETS = [BlsSecretKey(0x5EED + 7 * i) for i in range(4)]
KEYS = [sk.public_key().to_bytes() for sk in SECRETS]
POPS = [prove_possession(sk).to_bytes() for sk in SECRETS]


@pytest.fixture
def cold(monkeypatch):
    """An empty proof memo and key cache for the test, the process's own
    put back after it."""
    monkeypatch.setattr(service, "_POP_PASSED", set())
    monkeypatch.setattr(service, "_NATIVE_POP_KEYS", set())
    monkeypatch.setattr(service, "_PK_CACHE", {})


def committee(pops: list[bytes]) -> Committee:
    return Committee.new(
        [(PublicKey(pk), 1, ("127.0.0.1", 7100 + i)) for i, pk in enumerate(KEYS)],
        scheme="bls",
        pops={PublicKey(pk): pop for pk, pop in zip(KEYS, pops)},
    )


def spawn(com: Committee):
    """``Consensus.spawn`` as far as its first check: a refused committee
    never reaches the store or the network."""
    return asyncio.run(
        Consensus.spawn(
            PublicKey(KEYS[0]), com, Parameters(),
            BlsSigningService(SECRETS[0]), None, None,
            verifier=BlsVerifier(),
        )
    )  # fmt: skip


@pytest.mark.parametrize("memo", ["cold", "warm"])
def test_a_bad_proof_is_refused_at_every_spawn(memo, cold):
    """Member 2 carries member 1's proof.  Whether or not the good
    members' proofs are already in the memo, each spawn refuses it."""
    if memo == "warm":
        committee(POPS).verify_pops()
        assert len(service._POP_PASSED) == len(KEYS)
    bad = committee([POPS[0], POPS[1], POPS[1], POPS[3]])
    for _ in range(2):
        with pytest.raises(InvalidCommittee, match="invalid BLS proof"):
            spawn(bad)
    assert (KEYS[2], POPS[1]) not in service._POP_PASSED


def test_a_memo_hit_never_covers_another_pair(cold, monkeypatch):
    checks = []
    real = service.possession_holds
    monkeypatch.setattr(
        service, "possession_holds",
        lambda pk, pop, native=None: checks.append(pk) or real(pk, pop, native),
    )  # fmt: skip
    assert check_possession(KEYS[0], POPS[0])
    assert check_possession(KEYS[0], POPS[0])
    assert checks == [KEYS[0]]  # the second answer came from the memo
    # the same key with another proof, the same proof with another key,
    # and one flipped bit: each checked, each refused
    flipped = bytes([POPS[0][0] ^ 0x01]) + POPS[0][1:]
    for pk, pop in ((KEYS[0], POPS[1]), (KEYS[1], POPS[0]), (KEYS[0], flipped)):
        assert not check_possession(pk, pop)
        assert not check_possession(pk, pop)
    assert len(checks) == 7
    assert service._POP_PASSED == {(KEYS[0], POPS[0])}


def test_native_and_pure_python_checks_agree(cold):
    native = pytest.importorskip("hotstuff_tpu.crypto.bls.native")
    cases = [(pk, pop, True) for pk, pop in zip(KEYS, POPS)] + [
        (KEYS[0], POPS[1], False),
        (KEYS[1], POPS[0], False),
        (KEYS[2], bytes([0xC0]) + bytes(47), False),  # the identity
        (KEYS[3], POPS[3][:47] + bytes([POPS[3][47] ^ 0x80]), False),
        (KEYS[3][:95] + bytes([KEYS[3][95] ^ 0x01]), POPS[3], False),
    ]
    for pk, pop, valid in cases:
        assert possession_holds(pk, pop, native=native) is valid
        assert possession_holds(pk, pop, native=None) is valid


def test_eight_verifiers_decode_each_key_once(cold, monkeypatch):
    decodes = []
    real = service.BlsPublicKey.from_bytes
    monkeypatch.setattr(
        service.BlsPublicKey, "from_bytes",
        lambda data, **kw: decodes.append(data) or real(data, **kw),
    )  # fmt: skip
    verifiers = [BlsVerifier() for _ in range(8)]
    for v in verifiers:
        v.precompute(KEYS)
    assert sorted(decodes) == sorted(KEYS)
    assert len(service._PK_CACHE) == len(KEYS)


def test_a_key_whose_proof_passed_natively_skips_the_ladder(cold, monkeypatch):
    """The native proof check subgroup-checks the key, so its decode for
    the cache skips the pure-Python ladder; a key decoded before any
    proof of it passed (or checked in pure Python) keeps the ladder."""
    pytest.importorskip("hotstuff_tpu.crypto.bls.native")
    checked = []
    real = service.BlsPublicKey.from_bytes
    monkeypatch.setattr(
        service.BlsPublicKey, "from_bytes",
        lambda data, **kw: checked.append(kw["subgroup_check"]) or real(data, **kw),
    )  # fmt: skip
    assert check_possession(KEYS[0], POPS[0])
    assert service.decoded_key(KEYS[0]).to_bytes() == KEYS[0]
    assert service.decoded_key(KEYS[1]).to_bytes() == KEYS[1]
    assert checked == [False, True]
    assert not check_possession(KEYS[2], POPS[0])  # another key's proof
    assert KEYS[2] not in service._NATIVE_POP_KEYS


def test_warmup_runs_once_a_process_and_prints_the_warm_line(
    cold, monkeypatch, caplog
):
    """The device aggregator's one program (XLA:CPU here) for a 64-node
    committee: the running-sum add, checked against the host's sum, once
    however many verifiers warm, and no aggregation tree (a quorum check
    sums natively); the line is the one the benchmark's log reader
    parses."""
    monkeypatch.setattr(BlsVerifier, "_warm", set())
    caplog.set_level(logging.INFO, logger=service.__name__)
    committee_keys = [
        BlsSecretKey(0xC0DE + i).public_key().to_bytes() for i in range(64)
    ]
    verifiers = [BlsVerifier(aggregator="tpu") for _ in range(3)]
    for v in verifiers:
        v.precompute(committee_keys)
        v.warmup(batch=1024)
    lines = [r.getMessage() for r in caplog.records if " warm in " in r.getMessage()]
    assert len(lines) == 1
    log = CommitteeLog()
    log.feed(f"2026-01-01T00:00:00.000Z [INFO] {service.__name__} {lines[0]}")
    seconds, described = log.warm
    assert seconds >= 0
    assert described["kernel"] == "g1-xla"
    assert described["pad_shapes"] == []
    assert set(described["warm"]) == {"running_add"}
    for report in described["warm"].values():
        assert {"first_call_s", "cache_hits", "cache_misses"} <= set(report)
    # the CPU verifier has nothing to warm and prints nothing
    caplog.clear()
    cpu = BlsVerifier()
    cpu.precompute(KEYS)
    cpu.warmup(batch=64)
    assert not [r for r in caplog.records if " warm in " in r.getMessage()]


def test_a_second_boot_finds_the_running_add_in_the_compile_cache(tmp_path):
    """The add compiles faster than jax's threshold for writing the
    persistent cache; the warm-up writes it all the same, so the next
    process's warm report counts hits and no miss (the benchmark's
    ``verifier.cache_hits`` reads them), and the threshold is jax's
    again afterwards."""
    code = (
        "import json, jax\n"
        "from hotstuff_tpu.tpu.bls import warm_g1_programs\n"
        "report = warm_g1_programs()['running_add']\n"
        "print(json.dumps([report['cache_hits'], report['cache_misses'],"
        " jax.config.jax_persistent_cache_min_compile_time_secs]))\n"
    )
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    reads = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
            text=True, env=env, timeout=120,
        )  # fmt: skip
        assert done.returncode == 0, done.stderr[-2000:]
        reads.append(json.loads(done.stdout.strip().splitlines()[-1]))
    (hits, misses, threshold), (hits2, misses2, threshold2) = reads
    assert hits == 0 and misses > 0
    assert hits2 == misses and misses2 == 0
    import jax

    assert threshold == threshold2 == (
        jax.config.jax_persistent_cache_min_compile_time_secs
    )


def test_the_running_sum_follows_the_verifier(monkeypatch):
    """jax is imported (tests run on XLA:CPU), and still the CPU verifier
    gives the host's add; the device aggregator's verifier gives the
    device running sum."""
    import sys

    monkeypatch.delenv("HOTSTUFF_AGG_DEVICE_SUM", raising=False)
    assert "jax" in sys.modules
    assert _SigAccumulator(BlsVerifier())._device is None
    assert _SigAccumulator(BlsVerifier())._host is not None
    assert _SigAccumulator(BlsVerifier(aggregator="tpu"))._device is not None
    assert _SigAccumulator(None)._device is None
