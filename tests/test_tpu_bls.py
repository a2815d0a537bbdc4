"""TPU BLS12-381 G1 arithmetic and running sum vs the pure-Python oracle
(crypto/bls/curve.py), incl. the adversarial edge cases the branchless
point addition must handle (equal points, opposite points, identity)."""

import random

import numpy as np
import pytest

import jax
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


# ---- the fold: staged votes, one program a buffer ------------------------------

#: one row, two, one short of a full buffer, a full one, one past it, and
#: a 256-node quorum (two full buffers and 43)
FOLD_SIZES = [1, 2, 63, 64, 65, 171]
#: the program that made one add a vote before the fold (XLA:CPU here)
SINGLE = jax.jit(T._running_add_xla)


def identity_rows():
    return (
        jnp.zeros((1, T.NLIMBS), jnp.int32),
        jnp.asarray(T.to_mont_limbs(1), jnp.int32).reshape(1, T.NLIMBS),
        jnp.zeros((1, T.NLIMBS), jnp.int32),
    )


def plain_rows(pt: G1Point):
    """A vote's point as the single-add program took it: plain (x, y, 1)
    limb rows."""
    z = np.zeros((1, T.NLIMBS), np.int32)
    z[0, 0] = 1
    return (T.ints_to_limbs_batch([pt.x]), T.ints_to_limbs_batch([pt.y]), z)


def seeded_points(n: int, seed: int) -> list[G1Point]:
    """``n`` distinct seeded multiples of G."""
    rs = random.Random(seed)
    return [G1Point.generator().mul(rs.randrange(1, 2**64)) for _ in range(n)]


def check_fold_is_single_adds(n, donate, monkeypatch, fold=None, single=SINGLE):
    """``TpuG1RunningSum`` over ``n`` seeded points with an identity
    staged among them (skipped), its fold program ``fold`` where given
    (else the module's own) and donation as asked: after the snapshot
    the accumulator's limbs are those of ``n`` calls of ``single``, one
    a point, the affine sum is the host's, and a donated accumulator's
    first buffers are gone."""
    points = seeded_points(n, seed=0xF01D + n)
    points.insert(n // 2, G1Point.identity())
    monkeypatch.setattr(T, "_DONATE", donate)
    if fold is not None:
        name = "_running_add_kernel_donated" if donate else "_running_add_kernel"
        monkeypatch.setattr(T, name, fold)
    acc = T.TpuG1RunningSum()
    first = acc._acc
    total = G1Point.identity()
    want = identity_rows()
    for pt in points:
        acc.add(pt)
        total = total + pt
        if not pt.inf:
            want = single(*want, *plain_rows(pt))
    assert acc.snapshot() == total
    assert len(acc) == n
    assert all(np.array_equal(u, v) for u, v in zip(acc._acc, want))
    assert all(a.is_deleted() for a in first) == donate


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
@pytest.mark.parametrize("n", FOLD_SIZES)
def test_the_fold_is_successive_single_adds(n, donate, monkeypatch):
    """The XLA fold (what the running sum runs off the chip), a buffer of
    ``FOLD_ROWS`` at a time and the rest at the snapshot, bit for bit
    the single-add program's chain; the rows a fold leaves from an
    earlier one behind its count are not read."""
    check_fold_is_single_adds(n, donate, monkeypatch)


def test_the_buffer_is_staged_again_the_moment_a_fold_is_dispatched():
    """The running sum restages its buffer while the fold it just
    dispatched may not have read it yet: what the fold sums is what was
    staged when it was dispatched, however soon the buffer is written
    over (a transfer that read the buffer late summed the new rows in
    some of these tries)."""
    g = G1Point.generator()
    points = [g.mul(k) for k in range(1, T.FOLD_ROWS + 1)]
    for _ in range(30):
        acc = T.TpuG1RunningSum()
        for pt in points:
            acc.add(pt)
        acc._staged[:] = 7
        assert acc.snapshot() == g.mul(T.FOLD_ROWS * (T.FOLD_ROWS + 1) // 2)


@pytest.mark.parametrize("n, calls", [(1, 1), (43, 1), (64, 1), (65, 2), (171, 3)])
def test_a_fold_is_one_device_call(n, calls):
    """``device_calls`` of the ``BLS stats:`` line: one a full buffer,
    inside the add that fills it, and one at the snapshot for the rest;
    none for a snapshot with nothing staged."""
    from hotstuff_tpu.telemetry.blsstats import BLS_COUNTS, FIELDS

    assert "device_calls" in FIELDS
    before = BLS_COUNTS.counts["device_calls"]
    acc = T.TpuG1RunningSum()
    for pt in seeded_points(n, seed=n):
        acc.add(pt)
    assert BLS_COUNTS.counts["device_calls"] - before == n // T.FOLD_ROWS
    acc.snapshot()
    acc.snapshot()
    assert BLS_COUNTS.counts["device_calls"] - before == calls
    assert f"device_calls={BLS_COUNTS.counts['device_calls']}" in BLS_COUNTS.line()


@pytest.mark.parametrize("n", [43, 171])
def test_the_accumulators_aggregate_is_the_references(n, monkeypatch):
    """A QC maker's ``_SigAccumulator`` on the device over a 64-node
    quorum's and a 256-node quorum's seeded vote signatures: the
    compressed aggregate is ``crypto/bls_g1_ref.py``'s sum."""
    import hashlib

    from hotstuff_tpu.consensus.aggregator import _SigAccumulator
    from hotstuff_tpu.crypto import Signature
    from hotstuff_tpu.crypto import bls_g1_ref as ref
    from hotstuff_tpu.crypto.bls.service import BlsVerifier

    monkeypatch.delenv("HOTSTUFF_AGG_DEVICE_SUM", raising=False)
    digest = hashlib.sha256(b"fold quorum").digest()
    sigs = [BlsSecretKey(0xF01D + i).sign(digest).to_bytes() for i in range(n)]
    acc = _SigAccumulator(BlsVerifier(aggregator="tpu"))
    assert acc._device is not None
    assert all(acc.add(Signature(sig)) for sig in sigs)
    assert acc.aggregate() == ref.sum_compressed(sigs)


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
