"""Aggregated compact certificates (ISSUE 9): parity with the vote-list
form, the QC-verify memo, the device running sum, and the async claims
routing.

The load-bearing property is VERDICT PARITY: for every input — honest
quorum, forged certificate, equivocating twin — the compact form (one
aggregate + signer bitmap, one pairing) and the vote-list form (n
signatures, batch pairing) must accept and reject IDENTICALLY at every
committee size.  A divergence in either direction is a safety bug (the
aggregate path accepting what the batch path rejects) or a liveness bug
(the reverse).
"""

from __future__ import annotations

import time

import pytest

from hotstuff_tpu.consensus.config import Committee
from hotstuff_tpu.consensus.errors import ConsensusError
from hotstuff_tpu.consensus.messages import (
    QC,
    QC_CACHE_STATS,
    TC,
    Vote,
    bitmap_indices,
    bitmap_keys,
    make_signer_bitmap,
    timeout_digest,
)
from hotstuff_tpu.crypto import Digest, PublicKey, Signature
from hotstuff_tpu.crypto.bls import BlsSecretKey, prove_possession
from hotstuff_tpu.crypto.bls.curve import G1Point
from hotstuff_tpu.crypto.scheme import make_cpu_verifier


def bls_committee(n: int, base_port: int = 24_000):
    """(committee, {pk: sk}) with small-scalar secrets — fixture cost is
    O(n) cheap multiplies, verification cost is the real thing."""
    sks = [BlsSecretKey(i + 2) for i in range(n)]
    by_pk = {PublicKey(sk.public_key().to_bytes()): sk for sk in sks}
    com = Committee.new(
        [
            (pk, 1, ("127.0.0.1", base_port + i))
            for i, pk in enumerate(sorted(by_pk))
        ],
        scheme="bls",
        pops={pk: prove_possession(sk).to_bytes() for pk, sk in by_pk.items()},
    )
    return com, by_pk


def quorum_votes(com, by_pk, digest, round_=3):
    """Quorum-many (pk, sig) pairs over the QC digest for (digest, round)."""
    msg = QC(hash=digest, round=round_).digest().to_bytes()
    return [
        (pk, Signature(by_pk[pk].sign(msg).to_bytes()))
        for pk in com.sorted_keys()[: com.quorum_threshold()]
    ]


def compact_from(votes, com, digest, round_=3) -> QC:
    agg = G1Point.sum(
        [
            G1Point.from_bytes(sig.to_bytes(), subgroup_check=False)
            for _, sig in votes
        ]
    ).to_bytes()
    return QC(
        hash=digest,
        round=round_,
        votes=[],
        agg_sig=Signature(agg),
        signers=make_signer_bitmap(
            [pk for pk, _ in votes], com.sorted_keys()
        ),
    )


def verdict(qc: QC, com, verifier) -> bool:
    try:
        qc.check_weight(com)
        qc.verify(com, verifier)
        return True
    except ConsensusError:
        return False


@pytest.mark.parametrize("n", [4, 16, 64])
def test_compact_votelist_verdict_parity(n):
    """Identical accept/reject at committee sizes 4/16/64 for honest,
    forged and wrong-digest certificates — both forms, same verdicts."""
    com, by_pk = bls_committee(n)
    verifier = make_cpu_verifier("bls")
    digest = Digest.of(f"parity-{n}".encode())
    votes = quorum_votes(com, by_pk, digest)

    honest_list = QC(hash=digest, round=3, votes=list(votes))
    honest_compact = compact_from(votes, com, digest)
    assert honest_compact.wire_size() < honest_list.wire_size()
    assert verdict(honest_list, com, verifier) is True
    assert verdict(honest_compact, com, verifier) is True

    # a quorum's signatures over a DIFFERENT digest: both forms reject
    other = Digest.of(f"equivocating-twin-{n}".encode())
    wrong_list = QC(hash=other, round=3, votes=list(votes))
    wrong_compact = QC(
        hash=other,
        round=3,
        votes=[],
        agg_sig=honest_compact.agg_sig,
        signers=honest_compact.signers,
    )
    assert verdict(wrong_list, com, verifier) is False
    assert verdict(wrong_compact, com, verifier) is False

    # one flipped signature / one flipped aggregate byte: both reject
    bad_sig = bytearray(votes[0][1].to_bytes())
    bad_sig[5] ^= 0xFF
    tampered_list = QC(
        hash=digest,
        round=3,
        votes=[(votes[0][0], Signature(bytes(bad_sig)))] + votes[1:],
    )
    bad_agg = bytearray(honest_compact.agg_sig.to_bytes())
    bad_agg[5] ^= 0xFF
    tampered_compact = QC(
        hash=digest,
        round=3,
        votes=[],
        agg_sig=Signature(bytes(bad_agg)),
        signers=honest_compact.signers,
    )
    assert verdict(tampered_list, com, verifier) is False
    assert verdict(tampered_compact, com, verifier) is False


def test_adversary_forgeries_fail_both_forms():
    """faults/adversary.py's forged certificates keep failing against
    the aggregate path: forged_qc (vote-list garbage) and its compact
    twin forged_compact_qc both pass check_weight and both die in
    verification."""
    from hotstuff_tpu.faults.adversary import AdversaryPlane

    com, by_pk = bls_committee(4)
    plane = AdversaryPlane(
        {
            "name": "byz-forge-agg",
            "seed": 11,
            "epoch_unix": time.time(),
            "nodes": {f"127.0.0.1:{24_000 + i}": i for i in range(4)},
            "adversary": [{"policy": "forge-qc", "node": 0, "at": 0.0}],
        },
        ("127.0.0.1", 24_000),
    )
    verifier = make_cpu_verifier("bls")
    compact = plane.forged_compact_qc(com, 9)
    assert compact.is_compact
    compact.check_weight(com)  # structurally a quorum, by construction
    assert verdict(compact, com, verifier) is False
    # the compact forgery round-trips the wire like any real certificate
    from hotstuff_tpu.consensus.wire import decode_message, encode_tc

    tc = TC(round=9, votes=[], groups=None)
    assert not tc.is_compact  # sanity on the flag itself

    # the vote-list forgery still fails too (BLS sigs are 48B; the
    # plane draws 64B garbage — rejected before crypto by the wire
    # rules, and by crypto here)
    forged = plane.forged_qc(com, 9)
    assert verdict(forged, com, verifier) is False


def test_qc_verify_memoized_by_digest():
    """The same certificate arriving via Propose, sync and TC high-QCs
    is verified ONCE per cache: the second verify is a cache hit
    (qc_verify_cache_hit telemetry) and skips crypto entirely."""
    com, by_pk = bls_committee(4)
    verifier = make_cpu_verifier("bls")
    digest = Digest.of(b"memo block")
    votes = quorum_votes(com, by_pk, digest)
    qc = compact_from(votes, com, digest)

    cache: set = set()
    before = dict(QC_CACHE_STATS)
    qc.verify(com, verifier, cache=cache)
    assert len(cache) == 1
    assert QC_CACHE_STATS["misses"] == before["misses"] + 1

    # a BYTE-IDENTICAL copy (fresh object) hits the memo
    copy = compact_from(votes, com, digest)

    class Exploding:
        def __getattr__(self, name):  # any crypto call is a test failure
            raise AssertionError("cache hit must not touch the verifier")

    copy.verify(com, Exploding(), cache=cache)
    assert QC_CACHE_STATS["hits"] == before["hits"] + 1

    # claims() honours the same memo: no claims for a cached certificate
    assert copy.claims(cache=cache, committee=com) == []
    assert QC_CACHE_STATS["hits"] == before["hits"] + 2

    # a DIFFERENT certificate (vote-list form of the same quorum) has
    # its own key — compact and vote-list forms never collide
    aslist = QC(hash=digest, round=3, votes=list(votes))
    assert aslist._cache_key() not in cache
    aslist.verify(com, verifier, cache=cache)
    assert len(cache) == 2


def test_running_sum_matches_host_aggregate():
    """TpuG1RunningSum: k incremental device adds equal the host
    G1Point.sum of the same points, including past the naive chained-add
    overflow depth (the _freshen guard)."""
    jnp = pytest.importorskip("jax.numpy")  # noqa: F841 (jax gate)
    from hotstuff_tpu.tpu.bls import TpuG1RunningSum

    com, by_pk = bls_committee(4)
    digest = Digest.of(b"running sum")
    msg = QC(hash=digest, round=3).digest().to_bytes()
    # 60 points (> the ~40-50 chained-add overflow depth) from repeated
    # small-scalar signatures
    pts = [
        G1Point.from_bytes(
            BlsSecretKey(i + 2).sign(msg).to_bytes(), subgroup_check=False
        )
        for i in range(12)
    ] * 5
    acc = TpuG1RunningSum()
    for p in pts:
        acc.add(p)
    assert len(acc) == len(pts)
    assert acc.snapshot().to_bytes() == G1Point.sum(pts).to_bytes()
    acc.reset()
    assert len(acc) == 0


def test_aggregator_emits_compact_and_invalidates_on_replacement():
    """The vote Aggregator emits the compact form for BLS committees,
    counts it, records qc_bytes — and a replaced vote (equivocation
    repair) invalidates the running accumulator so the emitted aggregate
    still matches the surviving vote set."""
    from hotstuff_tpu.consensus.aggregator import Aggregator

    com, by_pk = bls_committee(4)
    verifier = make_cpu_verifier("bls")
    agg = Aggregator(com, verifier)
    bh = Digest.of(b"agg emission block")

    def signed(pk, h, r=5):
        v = Vote(hash=h, round=r, author=pk)
        v.signature = Signature(by_pk[pk].sign(v.digest().to_bytes()).to_bytes())
        return v

    ordered = com.sorted_keys()
    qc = None
    # first voter equivocates: same round, different digest, then the
    # real one — the maker replaces/evicts, the accumulator must follow
    agg.add_vote(signed(ordered[0], Digest.of(b"equivocation")), current_round=5)
    for pk in ordered[: com.quorum_threshold()]:
        qc = agg.add_vote(signed(pk, bh), current_round=5) or qc
    assert qc is not None and qc.is_compact
    qc.check_weight(com)
    qc.verify(com, verifier)  # the aggregate matches the final vote set
    assert agg.compact_qcs == 1
    assert agg.qc_wire_bytes == qc.wire_size()
    assert agg.stats()["compact_qcs_total"] == 1
    assert agg.stats()["qc_wire_bytes"] == qc.wire_size()

    # env kill-switch: HOTSTUFF_COMPACT_QC=0 reverts to vote lists
    import os

    os.environ["HOTSTUFF_COMPACT_QC"] = "0"
    try:
        agg2 = Aggregator(com, verifier)
        qc2 = None
        for pk in ordered[: com.quorum_threshold()]:
            qc2 = agg2.add_vote(signed(pk, bh, r=6), current_round=6) or qc2
        assert qc2 is not None and not qc2.is_compact
        qc2.verify(com, verifier)
    finally:
        del os.environ["HOTSTUFF_COMPACT_QC"]


def test_compact_tc_from_timeout_quorum():
    """TCMaker's compact form: per-high-qc-round groups, quorum weight
    across groups, verdict parity with the vote-list TC."""
    from hotstuff_tpu.consensus.aggregator import Aggregator
    from hotstuff_tpu.consensus.messages import Timeout

    com, by_pk = bls_committee(4)
    verifier = make_cpu_verifier("bls")
    agg = Aggregator(com, verifier)
    ordered = com.sorted_keys()
    # authors split over two high_qc rounds (0 and 2)
    highs = {ordered[0]: 0, ordered[1]: 2, ordered[2]: 2}
    tc = None
    for pk in ordered[:3]:
        t = Timeout(high_qc=QC(round=highs[pk]), round=8, author=pk)
        t.signature = Signature(
            by_pk[pk].sign(t.digest().to_bytes()).to_bytes()
        )
        tc = agg.add_timeout(t) or tc
    assert tc is not None and tc.is_compact
    assert sorted(tc.high_qc_rounds()) == [0, 2, 2]
    tc.verify(com, verifier)  # must not raise
    assert agg.compact_tcs == 1

    # tamper one group's aggregate: rejected, like a bad vote-list TC
    g = tc.groups
    bad = TC(
        round=8,
        votes=[],
        groups=[(g[0][0], Signature(b"\x13" * 48), g[0][2])] + g[1:],
    )
    with pytest.raises(ConsensusError):
        bad.verify(com, verifier)


def test_async_claims_route_agg():
    """'agg' claims take the one-pairing path through eval_claims_sync
    on both the aggregate-preferring (BLS) backend and via graceful
    False on a backend without aggregate support; claim_sig_count
    reports signer counts, not blob lengths."""
    from hotstuff_tpu.crypto.async_service import (
        claim_sig_count,
        eval_claims_sync,
    )

    com, by_pk = bls_committee(4)
    verifier = make_cpu_verifier("bls")
    digest = Digest.of(b"claims block")
    votes = quorum_votes(com, by_pk, digest)
    qc = compact_from(votes, com, digest)
    claims = qc.claims(committee=com)
    assert len(claims) == 1 and claims[0][0] == "agg"
    assert claim_sig_count(claims[0]) == len(votes)  # signers, not 48

    assert eval_claims_sync(verifier, claims) == [True]
    bad = (
        "agg",
        claims[0][1],
        b"\x77" * 48,
        claims[0][3],
    )
    # mixed wave: the bad aggregate fails, the good one still passes
    assert eval_claims_sync(verifier, [bad, claims[0]]) == [False, True]

    # an ed25519 backend has no aggregate form: claim resolves False
    # (never a crash, never a silent accept)
    ed = make_cpu_verifier("ed25519")
    assert eval_claims_sync(ed, claims) == [False]


def test_committee_scheme_selects_wire_form():
    """ed25519 committees keep the vote-list form end to end: the
    Aggregator never emits compact, and Committee.scheme drives it."""
    from hotstuff_tpu.consensus.aggregator import Aggregator
    from hotstuff_tpu.crypto import generate_keypair

    pairs = [generate_keypair(bytes(32), i) for i in range(4)]
    pairs.sort(key=lambda kp: kp[0])
    com = Committee.new(
        [
            (pk, 1, ("127.0.0.1", 25_000 + i))
            for i, (pk, _) in enumerate(pairs)
        ]
    )
    assert com.scheme == "ed25519"
    verifier = make_cpu_verifier("ed25519")
    agg = Aggregator(com, verifier)
    bh = Digest.of(b"ed25519 block")
    qc = None
    for pk, sk in pairs[:3]:
        v = Vote(hash=bh, round=4, author=pk)
        v.signature = Signature.new(v.digest(), sk)
        qc = agg.add_vote(v, current_round=4) or qc
    assert qc is not None and not qc.is_compact
    assert agg.compact_qcs == 0
    qc.verify(com, verifier)


def test_bitmap_helpers_roundtrip():
    """make_signer_bitmap / bitmap_indices / bitmap_keys agree for every
    subset size and preserve the committee order."""
    com, _ = bls_committee(16)
    ordered = com.sorted_keys()
    for k in (1, 5, 11, 16):
        subset = ordered[:k]
        bm = make_signer_bitmap(subset, ordered)
        assert len(bm) == 2  # ceil(16/8)
        assert list(bitmap_indices(bm)) == list(range(k))
        assert bitmap_keys(bm, ordered) == subset
    # scattered subset keeps ascending committee order regardless of
    # input order
    scattered = [ordered[9], ordered[1], ordered[14]]
    bm = make_signer_bitmap(scattered, ordered)
    assert bitmap_keys(bm, ordered) == [ordered[1], ordered[9], ordered[14]]
