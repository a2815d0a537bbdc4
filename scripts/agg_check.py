#!/usr/bin/env python3
"""Compact-certificate sweep (ISSUE 9): does the aggregated QC stay
O(1) and agree with the vote-list baseline across committee sizes?

For each committee size the check builds a BLS quorum over one block
digest and asserts, end to end:

  * PARITY — the compact QC (one aggregate + signer bitmap) and the
    vote-list QC produce identical accept verdicts, and the adversary
    plane's forged certificates (garbage aggregate over a valid quorum
    bitmap) are REJECTED by the aggregate path exactly as the vote-list
    forgery is by the batch path;
  * WIRE — compact wire size is 48 + ceil(n/8) + framing, i.e. constant
    in committee size up to the bitmap byte, vs n x 144 for vote lists;
  * FLATNESS — compact verify p50 (one pairing over the memoized key
    sum) at the largest size stays within ``--flat-ratio`` (default
    2.0) of the smallest — the one-pairing promise.

At the smallest size the quorum additionally flows through the REAL
``Aggregator`` (consensus/aggregator.py) so the running-sum emission
path is exercised, not just hand-built certificates.

Usage:
    python scripts/agg_check.py            # sizes 16,64,256
    AGG=1 scripts/trace.sh                 # same, via the trace wrapper
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_quorum(n: int, digest):
    """(sorted pks, quorum votes, running-sum aggregate bytes) with
    small-scalar secrets — fixture cost is O(n) cheap multiplies while
    verification cost is untouched."""
    from hotstuff_tpu.crypto import PublicKey, Signature
    from hotstuff_tpu.crypto.bls import BlsSecretKey
    from hotstuff_tpu.crypto.bls.curve import G1Point

    sks = [BlsSecretKey(i + 2) for i in range(n)]
    by_pk = {PublicKey(sk.public_key().to_bytes()): sk for sk in sks}
    pks = sorted(by_pk)
    quorum = 2 * n // 3 + 1
    msg = digest.to_bytes()
    votes = [
        (pk, Signature(by_pk[pk].sign(msg).to_bytes()))
        for pk in pks[:quorum]
    ]
    agg = G1Point.sum(
        [
            G1Point.from_bytes(sig.to_bytes(), subgroup_check=False)
            for _, sig in votes
        ]
    ).to_bytes()
    return pks, votes, agg


def check_size(n: int, reps: int) -> tuple[float, list[str]]:
    """(compact verify p50 ms, failure messages) for one committee."""
    from hotstuff_tpu.consensus.messages import QC, make_signer_bitmap
    from hotstuff_tpu.crypto import Digest, Signature
    from hotstuff_tpu.crypto.scheme import make_cpu_verifier

    fails: list[str] = []
    digest = Digest.of(f"agg-check-{n}".encode())
    pks, votes, agg = build_quorum(n, digest)
    signers = [pk for pk, _ in votes]
    pk_bytes = [pk.to_bytes() for pk in signers]
    verifier = make_cpu_verifier("bls")
    verifier.precompute(pk_bytes)

    compact = QC(
        hash=digest,
        round=3,
        votes=[],
        agg_sig=Signature(agg),
        signers=make_signer_bitmap(signers, pks),
    )
    votelist = QC(hash=digest, round=3, votes=list(votes))

    # parity: both forms accept the honest quorum
    ok_compact = bool(
        verifier.verify_aggregate_msg(digest, pk_bytes, agg)
    )
    ok_votelist = bool(verifier.verify_shared_msg(digest, votes))
    if not (ok_compact and ok_votelist):
        fails.append(
            f"n={n}: honest quorum verdicts diverge "
            f"(compact={ok_compact} votelist={ok_votelist})"
        )

    # parity: a garbage aggregate over the same valid bitmap must fail
    forged = bytearray(agg)
    forged[7] ^= 0xFF
    if verifier.verify_aggregate_msg(digest, pk_bytes, bytes(forged)):
        fails.append(f"n={n}: forged aggregate ACCEPTED")

    # wire: constant-size promise (agg sig + bitmap + fixed framing)
    cb, vb = compact.wire_size(), votelist.wire_size()
    bound = 48 + (len(pks) + 7) // 8 + 64  # framing slack
    if cb > bound:
        fails.append(f"n={n}: compact wire {cb}B exceeds bound {bound}B")
    if cb * 10 > vb and n >= 16:
        fails.append(
            f"n={n}: compact wire {cb}B not <10% of vote-list {vb}B"
        )

    # flatness sample: warm the key-sum memo, then p50 the pairing
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        assert verifier.verify_aggregate_msg(digest, pk_bytes, agg)
        samples.append((time.perf_counter() - t0) * 1e3)
    samples.sort()
    p50 = samples[len(samples) // 2]

    print(
        f"   n={n:4d}: compact {cb}B vs vote-list {vb}B, "
        f"verify p50 {p50:.2f} ms"
    )
    return p50, fails


def check_aggregator_path(n: int) -> list[str]:
    """Drive the smallest committee through the REAL Aggregator: the
    running-sum compact emission, the claims plane, and the adversary
    plane's compact forgery."""
    from hotstuff_tpu.consensus.aggregator import Aggregator
    from hotstuff_tpu.consensus.config import Committee
    from hotstuff_tpu.consensus.errors import ConsensusError
    from hotstuff_tpu.consensus.messages import Vote
    from hotstuff_tpu.crypto import Digest, PublicKey, Signature
    from hotstuff_tpu.crypto.bls import BlsSecretKey, prove_possession
    from hotstuff_tpu.crypto.scheme import make_cpu_verifier
    from hotstuff_tpu.faults.adversary import AdversaryPlane

    fails: list[str] = []
    sks = [BlsSecretKey(i + 2) for i in range(n)]
    by_pk = {PublicKey(sk.public_key().to_bytes()): sk for sk in sks}
    com = Committee.new(
        [
            (pk, 1, ("127.0.0.1", 21000 + i))
            for i, pk in enumerate(sorted(by_pk))
        ],
        scheme="bls",
        pops={
            pk: prove_possession(sk).to_bytes()
            for pk, sk in by_pk.items()
        },
    )
    verifier = make_cpu_verifier("bls")
    agg = Aggregator(com, verifier)
    bh = Digest.of(b"agg-check-aggregator-block")
    qc = None
    for pk in com.sorted_keys()[: com.quorum_threshold()]:
        vote = Vote(hash=bh, round=5, author=pk, signature=None)
        vote.signature = Signature(
            by_pk[pk].sign(vote.digest().to_bytes()).to_bytes()
        )
        qc = agg.add_vote(vote, current_round=5) or qc
    if qc is None or not qc.is_compact:
        fails.append(f"Aggregator did not emit a compact QC: {qc!r}")
        return fails
    try:
        qc.check_weight(com)
        qc.verify(com, verifier)
    except ConsensusError as e:
        fails.append(f"Aggregator-emitted compact QC rejected: {e}")

    plane = AdversaryPlane.__new__(AdversaryPlane)
    import random

    plane.seed = 7
    plane.rng = random.Random(7)
    forged = plane.forged_compact_qc(com, 6)
    try:
        forged.check_weight(com)  # structurally valid by design
        forged.verify(com, verifier)
        fails.append("forged compact QC ACCEPTED by verify")
    except ConsensusError:
        pass
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="16,64,256",
                    help="committee sizes (default 16,64,256)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--flat-ratio", type=float,
                    default=float(os.environ.get("AGG_FLAT_RATIO", "2.0")),
                    help="allowed compact verify p50 growth largest/"
                    "smallest (default 2.0, env AGG_FLAT_RATIO)")
    args = ap.parse_args(argv)
    sizes = tuple(int(x) for x in args.sizes.split(",") if x)

    print(" AGG CHECK — compact vs vote-list certificates per "
          "committee size")
    fails: list[str] = []
    p50s: dict[int, float] = {}
    for n in sizes:
        p50, f = check_size(n, args.reps)
        p50s[n] = p50
        fails += f
    fails += check_aggregator_path(min(sizes))

    lo, hi = min(sizes), max(sizes)
    ratio = p50s[hi] / max(p50s[lo], 1e-9)
    print(f"   flatness: p50 {p50s[lo]:.2f} ms @ {lo} -> "
          f"{p50s[hi]:.2f} ms @ {hi} (ratio {ratio:.2f}, "
          f"gate {args.flat_ratio:g})")
    if ratio > args.flat_ratio:
        fails.append(
            f"compact verify p50 grew {ratio:.2f}x from committee "
            f"{lo} to {hi} (gate {args.flat_ratio:g}) — the one-pairing "
            f"path has degraded"
        )

    if fails:
        print("agg_check: FAIL")
        for msg in fails:
            print(f"  - {msg}")
        return 1
    print("agg_check: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
