"""The compact QC of ``BASELINE.json`` config 5's 256-member BLS
committee on the CPU: seeded keys, 171 votes signed natively, their sum
kept by the device's running sum (``_SigAccumulator`` under the device
aggregator, XLA:CPU here), the signer bitmap, the aggregate against the
benchmark's own reference, the certificate through ``BlsVerifier``, and
the quorum check at 171 unverified votes, which sums them natively."""

import hashlib
import random

import pytest

from chipbench.reference import bls_g1_ref
from hotstuff_tpu.consensus.aggregator import QCMaker, _SigAccumulator
from hotstuff_tpu.consensus.config import Committee
from hotstuff_tpu.consensus.messages import Vote
from hotstuff_tpu.crypto import Digest, Signature
from hotstuff_tpu.crypto.bls.service import BlsSigningService, BlsVerifier
from hotstuff_tpu.crypto.scheme import keygen_deterministic

NODES, QUORUM = 256, 171
SEED = 2147490044


@pytest.fixture(scope="module")
def committee():
    """256 seeded members, each with its signing service."""
    key_seed = hashlib.sha256(f"bls256 qc {SEED}".encode()).digest()
    members = [keygen_deterministic("bls", key_seed, i) for i in range(NODES)]
    com = Committee.new(
        [(pk, 1, ("127.0.0.1", 9000 + i)) for i, (pk, _) in enumerate(members)],
        scheme="bls",
    )
    signers = {pk: BlsSigningService(secret.to_bytes()) for pk, secret in members}
    return com, signers


def votes_of(committee, round_: int, block: Digest, voters) -> list[Vote]:
    com, signers = committee
    votes = []
    for pk in voters:
        vote = Vote(hash=block, round=round_, author=pk)
        vote.signature = signers[pk].sign_sync(vote.digest())
        votes.append(vote)
    return votes


def quorum_of(committee, rng: random.Random) -> list:
    return rng.sample(committee[0].sorted_keys(), QUORUM + 1)


def test_the_committee_and_its_quorum(committee):
    com, _ = committee
    assert len(com.sorted_keys()) == NODES
    assert com.quorum_threshold() == QUORUM == 2 * ((NODES - 1) // 3) + 1


def test_171_votes_summed_on_the_device_make_the_reference_qc(committee):
    com, _ = committee
    verifier = BlsVerifier(aggregator="tpu")
    voters = quorum_of(committee, random.Random(SEED))[:QUORUM]
    block = Digest.of(b"bls256 block 7")
    votes = votes_of(committee, 7, block, voters)
    assert all(s._native_sign is not None for s in committee[1].values())

    maker, qc = QCMaker(), None
    for vote in votes:
        qc = maker.append(vote, com, verifier, sig_verified=True)
    assert qc is not None and qc.is_compact
    assert maker._acc._device is not None  # the device's running sum
    assert len(maker._acc._device) == QUORUM

    # 171 bits set over the 256 sorted keys, exactly the voters
    assert len(qc.signers) == NODES // 8
    assert sum(bin(b).count("1") for b in qc.signers) == QUORUM
    assert set(qc.signer_keys(com)) == set(voters)
    # the aggregate is the reference's sum of the vote signatures
    sigs = [v.signature.to_bytes() for v in votes]
    assert qc.agg_sig.to_bytes() == bls_g1_ref.sum_compressed(sigs)
    qc.verify(com, verifier)


def test_the_quorum_check_at_171_unverified_votes(committee):
    """A valid set of 171 unverified votes makes a QC at the 171st; one
    bad signature among them is found and evicted at quorum, and the
    next valid vote makes the QC over the 171 that are good."""
    com, signers = committee
    verifier = BlsVerifier(aggregator="tpu")
    block = Digest.of(b"bls256 block 8")

    voters = quorum_of(committee, random.Random(SEED + 1))
    votes = votes_of(committee, 8, block, voters)
    maker = QCMaker()
    made = [maker.append(vote, com, verifier) for vote in votes[:QUORUM]]
    assert made[:-1] == [None] * (QUORUM - 1)
    assert made[-1] is not None and made[-1].is_compact
    made[-1].verify(com, verifier)

    bad = 17  # the signer's own signature, of another block
    wrong = Vote(hash=Digest.of(b"another block"), round=8, author=voters[bad])
    votes[bad].signature = signers[voters[bad]].sign_sync(wrong.digest())
    maker = QCMaker()
    assert [maker.append(v, com, verifier) for v in votes[:QUORUM]] == [
        None
    ] * QUORUM
    assert maker.weight == QUORUM - 1
    assert voters[bad] not in maker.used
    qc = maker.append(votes[QUORUM], com, verifier)
    assert qc is not None and qc.is_compact
    assert voters[bad] not in qc.signer_keys(com)
    good = [v.signature.to_bytes() for i, v in enumerate(votes) if i != bad]
    assert qc.agg_sig.to_bytes() == bls_g1_ref.sum_compressed(good)
    qc.verify(com, verifier)


def test_a_bad_signature_fails_the_native_quorum_check(committee):
    """``verify_shared_msg`` itself, as the device aggregator's verifier
    runs it: True for 171 good votes, False with one signature swapped
    for a valid signature of another message, and for a blob that is
    not a point."""
    verifier = BlsVerifier(aggregator="tpu")
    block = Digest.of(b"bls256 block 9")
    voters = quorum_of(committee, random.Random(SEED + 2))[:QUORUM]
    votes = votes_of(committee, 9, block, voters)
    pairs = [(v.author, v.signature) for v in votes]
    digest = votes[0].digest()
    assert verifier.verify_shared_msg(digest, pairs)
    other = votes_of(committee, 10, block, voters[:1])[0].signature
    assert not verifier.verify_shared_msg(digest, [(voters[0], other)] + pairs[1:])
    junk = Signature(bytes([0xFF]) * 48)
    assert not verifier.verify_shared_msg(digest, pairs[:-1] + [(voters[-1], junk)])


def test_the_running_sum_is_exact_over_171_distinct_points(committee):
    """The device's running sum (XLA:CPU) over 171 seeded distinct
    signatures equals the reference's, add by add at a few depths."""
    block = Digest.of(b"bls256 block 11")
    voters = quorum_of(committee, random.Random(SEED + 3))[:QUORUM]
    sigs = [v.signature for v in votes_of(committee, 11, block, voters)]
    acc = _SigAccumulator(BlsVerifier(aggregator="tpu"))
    assert acc._device is not None
    for depth, sig in enumerate(sigs, 1):
        assert acc.add(sig)
        if depth in (1, 2, 43, 128, QUORUM):
            expected = bls_g1_ref.sum_compressed(s.to_bytes() for s in sigs[:depth])
            assert acc.aggregate() == expected, depth
