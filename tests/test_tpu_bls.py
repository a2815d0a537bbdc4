"""TPU BLS12-381 G1 arithmetic and running sum vs the pure-Python oracle
(crypto/bls/curve.py), incl. the adversarial edge cases the branchless
point addition must handle (equal points, opposite points, identity)."""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from hotstuff_tpu.crypto.bls import (
    BlsSecretKey,
    aggregate_signatures,
)
from hotstuff_tpu.crypto.bls.curve import G1Point
from hotstuff_tpu.crypto.bls.fields import P as Q
from hotstuff_tpu.tpu import bls as T

rng = random.Random(4242)


def rand_fq() -> int:
    return rng.randrange(Q)


def rand_point() -> G1Point:
    return G1Point.generator().mul(rng.randrange(1, 2**64))


def to_dev(x: int):
    return jnp.asarray(T.to_mont_limbs(x))[None, :]


def test_mont_roundtrip_and_mul():
    for _ in range(10):
        a, b = rand_fq(), rand_fq()
        assert T.from_mont_int(T.to_mont_limbs(a)) == a
        out = T.mont_mul(to_dev(a), to_dev(b))
        assert T.from_mont_int(np.asarray(out)[0]) == a * b % Q


def test_mont_mul_edge_values():
    cases = [(0, 0), (0, 1), (1, 1), (Q - 1, Q - 1), (Q - 1, 1), (2, Q - 2)]
    a = jnp.stack([jnp.asarray(T.to_mont_limbs(x)) for x, _ in cases])
    b = jnp.stack([jnp.asarray(T.to_mont_limbs(y)) for _, y in cases])
    out = np.asarray(T.mont_mul(a, b))
    for i, (x, y) in enumerate(cases):
        assert T.from_mont_int(out[i]) == x * y % Q, (x, y)


def test_mont_add_sub():
    for _ in range(10):
        a, b = rand_fq(), rand_fq()
        s = np.asarray(T.madd(to_dev(a), to_dev(b)))[0]
        d = np.asarray(T.msub(to_dev(a), to_dev(b)))[0]
        # Montgomery form is linear, so add/sub stay in-form
        assert T.from_mont_int(s) == (a + b) % Q
        assert T.from_mont_int(d) == (a - b) % Q


def _dev_point(pt: G1Point):
    if pt.inf:
        one = to_dev(1)
        return (jnp.zeros_like(one), one, jnp.zeros_like(one))
    return (to_dev(pt.x), to_dev(pt.y), to_dev(1))


def _read_point(p) -> G1Point:
    x, y, z = (np.asarray(c)[0] for c in p)
    return T.projective_to_affine(x, y, z)


@pytest.mark.parametrize(
    "case",
    ["distinct", "equal", "opposite", "p_inf", "q_inf", "both_inf"],
)
def test_point_add_unified(case):
    p = rand_point()
    if case == "distinct":
        q = rand_point()
    elif case == "equal":
        q = p
    elif case == "opposite":
        q = -p
    elif case == "p_inf":
        q, p = p, G1Point.identity()
    elif case == "q_inf":
        q = G1Point.identity()
    else:
        p = q = G1Point.identity()
    want = p + q
    got = _read_point(T.point_add(_dev_point(p), _dev_point(q)))
    assert got == want, case


def test_projective_to_affine_reads_any_scaling_back():
    """The host's read-back of a device point: (l*x : l*y : l) is (x, y)
    for any nonzero l, loose limbs (a multiple of q added) included, and
    a zero Z is the identity whatever X and Y hold."""
    p = rand_point()
    for lam in (1, 2, rng.randrange(2, Q)):
        rows = [T.to_mont_limbs(v * lam % Q) for v in (p.x, p.y, 1)]
        assert T.projective_to_affine(*rows) == p
    loose = [T._int_to_limbs(v * T.R_MONT % Q + Q) for v in (p.x, p.y, 1)]
    assert T.projective_to_affine(*loose) == p
    assert T.projective_to_affine(
        T.to_mont_limbs(7), T.to_mont_limbs(9), np.zeros(T.NLIMBS, np.int32)
    ) == G1Point.identity()


def test_point_add_doubles():
    for _ in range(3):
        p = rand_point()
        got = _read_point(T.point_add(_dev_point(p), _dev_point(p)))
        assert got == p + p


def _running_sum(points) -> G1Point:
    acc = T.TpuG1RunningSum()
    for pt in points:
        acc.add(pt)
    return acc.snapshot()


def test_aggregate_matches_cpu_backend():
    """The device's running sum == CPU aggregate_signatures on real vote
    sets, including duplicate signatures (adversarial re-submission)."""
    digest = b"\x07" * 32
    sks = [BlsSecretKey(100 + i) for i in range(7)]
    sigs = [sk.sign(digest) for sk in sks]
    sigs.append(sigs[0])  # duplicate
    want = aggregate_signatures(sigs).point
    assert _running_sum([s.point for s in sigs]) == want


def test_aggregate_identity_and_empty():
    assert _running_sum([]) == G1Point.identity()
    assert _running_sum([G1Point.identity()]) == G1Point.identity()
    p = rand_point()
    assert _running_sum([p, G1Point.identity()]) == p
    assert _running_sum([p, -p]) == G1Point.identity()


def test_bls_verifier_tpu_aggregation_end_to_end():
    """QC verify through BlsVerifier(aggregator='tpu') agrees with the
    CPU backend on valid and tampered vote sets."""
    from hotstuff_tpu.crypto.bls.service import BlsVerifier

    digest = b"\x21" * 32
    sks = [BlsSecretKey(7 + i) for i in range(4)]
    votes = [
        (sk.public_key().to_bytes(), sk.sign(digest).to_bytes())
        for sk in sks
    ]
    cpu, tpu = BlsVerifier(), BlsVerifier(aggregator="tpu")
    assert tpu.verify_shared_msg(digest, votes)
    assert cpu.verify_shared_msg(digest, votes)
    # tamper one signature: both backends must reject
    bad = votes[:2] + [(votes[2][0], votes[3][1])] + votes[3:]
    assert not tpu.verify_shared_msg(digest, bad)
    assert not cpu.verify_shared_msg(digest, bad)


def test_aggregate_deep_tree_stress():
    """40 distinct points through the running sum: a 40-deep chain of
    loose-on-loose additions, past the ~40-add magnitude drift that the
    accumulator's freshen exists for, where the CIOS overflow-column
    fold once dropped a carry (shifting the value by k*R)."""
    pts = [rand_point() for _ in range(40)]
    want = pts[0]
    for p in pts[1:]:
        want = want + p
    assert _running_sum(pts) == want


@pytest.mark.parametrize("kind", ["tpu-sharded", "mesh"])
def test_sharded_bls_verifier_end_to_end(kind):
    """BLS has no sharded device path (a QC's running sum lives on one
    device): asking a node for one is refused, by the verifier and by
    the node's factory, rather than served by the single-device sum
    under another name."""
    from hotstuff_tpu.crypto.bls.service import BlsVerifier
    from hotstuff_tpu.node.node import make_verifier

    with pytest.raises(ValueError, match="unknown BLS aggregator"):
        BlsVerifier(aggregator="tpu-sharded")
    with pytest.raises(ValueError, match="no 'tpu-sharded' device verifier"):
        make_verifier(kind, "bls")
    assert make_verifier("tpu", "bls").name == "bls-tpu"


def test_scalar_mult_ladder_matches_oracle():
    """The batched variable-base ladder (TpuG1ScalarMul) against the
    Python oracle, including chain depths past the ~40-add magnitude
    drift the per-iteration freshen exists for (a 48-bit ladder runs 96
    sequential point adds)."""
    from hotstuff_tpu.crypto.bls.curve import G1Point
    from hotstuff_tpu.tpu.bls import TpuG1ScalarMul

    g = G1Point.generator()
    g2 = g + g
    m = TpuG1ScalarMul(nbits=48)
    ks = [5, (1 << 40) + 1, (1 << 47) + (1 << 23) + 9, 0]
    pts = [g, g, g2, g]
    out = m.mul(ks, pts)
    for k, p, r in zip(ks, pts, out):
        want = p._mul_raw(k)
        assert r == want or (r.inf and want.inf)


def test_native_offload_split_apis():
    """The host ends of the storm offload: hash_base_many gives the
    PRE-cofactor map (base * h_eff == hash_to_g1), g1_decompress_many
    round-trips signatures, and verify_batch_points accepts the pairing
    product over correctly weighted points and rejects a corruption."""
    import secrets

    pytest.importorskip("hotstuff_tpu.crypto.bls.native")
    from hotstuff_tpu.crypto import Digest
    from hotstuff_tpu.crypto.bls import keygen as bls_keygen, native
    from hotstuff_tpu.crypto.bls.curve import H1, G1Point, hash_to_g1
    from hotstuff_tpu.crypto.bls.service import BlsSigningService

    n = 6
    db, pb, sb = [], [], []
    for i in range(n):
        pk, sk = bls_keygen(bytes([77, i]) + b"\x00" * 30)
        svc = BlsSigningService(sk)
        d = Digest.of(bytes([i]) * 7)
        db.append(d.to_bytes())
        pb.append(pk.to_bytes())
        sb.append(svc.sign_sync(d).to_bytes())

    def parse(raw, count):
        return [
            G1Point(
                int.from_bytes(raw[96 * i : 96 * i + 48], "big"),
                int.from_bytes(raw[96 * i + 48 : 96 * i + 96], "big"),
            )
            for i in range(count)
        ]

    bases = parse(native.hash_base_many(db), n)
    for d, base in zip(db, bases):
        assert base._mul_raw(H1) == hash_to_g1(d)
    sigs = parse(native.g1_decompress_many(sb), n)

    ws = [secrets.randbits(128) | 1 for _ in range(n)]
    whm = [bases[i]._mul_raw(ws[i] * H1) for i in range(n)]
    agg = G1Point.identity()
    for i in range(n):
        agg = agg + sigs[i]._mul_raw(ws[i])

    def ser(pt):
        return (
            bytes(96)
            if pt.inf
            else pt.x.to_bytes(48, "big") + pt.y.to_bytes(48, "big")
        )

    whm_bytes = b"".join(ser(p) for p in whm)
    assert native.verify_batch_points(whm_bytes, pb, ser(agg))
    # corrupt one weighted-hash point: product must fail
    bad = bytearray(whm_bytes)
    bad[50] ^= 1
    assert not native.verify_batch_points(bytes(bad), pb, ser(agg))
