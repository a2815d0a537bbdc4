"""The G1 oracle (``crypto/bls_g1_ref.py``) against the program's curve
code, and the program's vote sums against the oracle: the device running
sum and the host's add over seeded vote signatures, and the compact QC a
``QCMaker`` emits.  These are the comparisons the ``bls64`` cell makes on
the chip, at sizes the CPU carries."""

from __future__ import annotations

import hashlib

import pytest

from hotstuff_tpu.consensus.aggregator import QCMaker, _SigAccumulator
from hotstuff_tpu.consensus.config import Committee
from hotstuff_tpu.consensus.messages import QC, Vote, bitmap_indices
from hotstuff_tpu.crypto import Digest, PublicKey, Signature
from hotstuff_tpu.crypto import bls_g1_ref as ref
from hotstuff_tpu.crypto.bls import (
    BlsPublicKey,
    BlsSecretKey,
    BlsSignature,
    prove_possession,
    verify_aggregate,
)
from hotstuff_tpu.crypto.bls.curve import G1_X, G1_Y, G1Point
from hotstuff_tpu.crypto.bls.fields import P
from hotstuff_tpu.crypto.scheme import make_cpu_verifier

SEED = b"bls_g1_ref seed"


def seeded_scalar(i: int) -> int:
    return int.from_bytes(hashlib.sha256(SEED + bytes([i])).digest(), "big")


def vote_signatures(n: int) -> list[bytes]:
    """``n`` members' signatures over one vote digest, seeded keys."""
    msg = hashlib.sha256(SEED + b"vote").digest()
    return [
        BlsSecretKey(seeded_scalar(i)).sign(msg).to_bytes() for i in range(n)
    ]


def test_constants_are_the_curves():
    assert ref.P == P
    assert ref.G == (G1_X, G1_Y) and ref.on_curve(ref.G)


@pytest.mark.parametrize("k", [1, 2, 3, 0xD15EA5E, 2**200 + 7])
def test_round_trips_curve_encoding_on_seeded_points(k):
    point = G1Point.generator().mul(k * seeded_scalar(k % 251))
    data = point.to_bytes()
    assert ref.decompress(data) == (point.x, point.y)
    assert ref.compress(ref.decompress(data)) == data


def test_identity_and_the_group_law():
    identity = G1Point.identity().to_bytes()
    assert ref.decompress(identity) is None and ref.compress(None) == identity
    g = G1Point.generator()
    assert ref.sum_compressed([g.to_bytes(), (-g).to_bytes()]) == identity
    assert ref.double(ref.G) == ref.add(ref.G, ref.G) == ref.decompress(
        g.mul(2).to_bytes()
    )


@pytest.mark.parametrize(
    "data",
    [
        bytes(48),  # no compression flag
        bytes([0xE0]) + bytes(47),  # identity with the sign bit
        bytes([0xC0]) + bytes(46) + b"\x01",  # identity with a payload
        bytes([0x9A, 0x01, 0x12]) + bytes(45),  # x above the modulus
    ],
    ids=["uncompressed", "signed-identity", "dirty-identity", "x-too-big"],
)
def test_refuses_what_is_no_encoding(data):
    with pytest.raises(ValueError):
        ref.decompress(data)


def test_refuses_a_point_off_the_curve():
    """The first x past the generator's whose x^3 + 4 is no square: the
    encoding is well formed, the point does not exist."""
    x = G1_X + 1
    while pow((x**3 + 4) % P, (P - 1) // 2, P) == 1:
        x += 1
    data = bytearray(x.to_bytes(48, "big"))
    data[0] |= 0x80
    with pytest.raises(ValueError):
        ref.decompress(bytes(data))
    assert G1Point.from_bytes(bytes(data), subgroup_check=False) is None
    assert not ref.on_curve((x, 1))


class HostVerifier:
    sums_on_device = False


class DeviceVerifier:
    sums_on_device = True


@pytest.mark.parametrize("n", [1, 4, 7, 43])
@pytest.mark.parametrize("where", ["host", "device"])
def test_running_sum_equals_the_oracle(n, where, monkeypatch):
    """The host's Jacobian add and the device running sum (XLA:CPU here,
    the TPU in the cell) over ``n`` seeded vote signatures."""
    monkeypatch.delenv("HOTSTUFF_AGG_DEVICE_SUM", raising=False)
    verifier = DeviceVerifier() if where == "device" else HostVerifier()
    acc = _SigAccumulator(verifier)
    assert (acc._device is not None) == (where == "device")
    sigs = vote_signatures(n)
    for sig in sigs:
        assert acc.add(Signature(sig))
    assert acc.count == n
    assert acc.aggregate() == ref.sum_compressed(sigs)


def test_device_sum_forced_by_the_environment(monkeypatch):
    """HOTSTUFF_AGG_DEVICE_SUM=1 puts the sum on the device whatever the
    verifier, =0 keeps it on the host."""
    sigs = vote_signatures(4)
    monkeypatch.setenv("HOTSTUFF_AGG_DEVICE_SUM", "1")
    acc = _SigAccumulator(HostVerifier())
    assert acc._device is not None
    for sig in sigs:
        acc.add(Signature(sig))
    assert acc.aggregate() == ref.sum_compressed(sigs)
    monkeypatch.setenv("HOTSTUFF_AGG_DEVICE_SUM", "0")
    assert _SigAccumulator(DeviceVerifier())._device is None


def test_a_qc_maker_emits_the_oracles_aggregate():
    """Seven BLS votes of a ten-member committee: the seventh makes a
    compact QC whose aggregate is the oracle's sum of the seven vote
    signatures, which verifies under the pure-Python aggregate check
    with the keys its bitmap names."""
    sks = [BlsSecretKey(seeded_scalar(i)) for i in range(10)]
    by_pk = {PublicKey(sk.public_key().to_bytes()): sk for sk in sks}
    committee = Committee.new(
        [(pk, 1, ("127.0.0.1", 7000 + i)) for i, pk in enumerate(by_pk)],
        scheme="bls",
        pops={pk: prove_possession(sk).to_bytes() for pk, sk in by_pk.items()},
    )
    assert committee.quorum_threshold() == 7
    verifier = make_cpu_verifier("bls")
    block = Digest.of(b"bls_g1_ref block")
    maker, qc, sigs = QCMaker(), None, []
    for pk in committee.sorted_keys()[:7]:
        vote = Vote(hash=block, round=9, author=pk)
        vote.signature = Signature(
            by_pk[pk].sign(vote.digest().to_bytes()).to_bytes()
        )
        sigs.append(vote.signature.to_bytes())
        assert qc is None
        qc = maker.append(vote, committee, verifier, sig_verified=True)
    assert qc is not None and qc.is_compact
    assert qc.agg_sig.to_bytes() == ref.sum_compressed(sigs)
    ordered = committee.sorted_keys()
    signers = [
        BlsPublicKey.from_bytes(ordered[i].to_bytes())
        for i in bitmap_indices(qc.signers)
    ]
    assert len(signers) == 7
    message = QC(hash=block, round=9).digest().to_bytes()
    assert verify_aggregate(
        message, signers, BlsSignature.from_bytes(qc.agg_sig.to_bytes())
    )
