"""TPU ed25519 kernel tests: point ops and batch verification vs the
pure-Python oracle (crypto/ed25519_ref.py), incl. adversarial inputs."""

import hashlib
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hotstuff_tpu.crypto import ed25519_ref as ref
from hotstuff_tpu.tpu import curve, field as F
from hotstuff_tpu.tpu.ed25519 import BatchVerifier

rng = random.Random(99)

jadd_pt = jax.jit(curve.point_add)
jdbl_pt = jax.jit(curve.point_double)


def rand_point():
    """Random curve point = [r]B via the oracle."""
    return ref.point_mul(rng.randrange(1, ref.L), ref.B_POINT)


def to_dev_point(p):
    return tuple(jnp.asarray(v)[None, :] for v in curve.point_to_limbs(p))


def assert_same_point(dev_p, ref_p):
    x = F.int_from_limbs(jax.jit(F.canonical)(F.mul(dev_p[0], jax.jit(F.pow_inv)(dev_p[2])))[0])
    y = F.int_from_limbs(jax.jit(F.canonical)(F.mul(dev_p[1], jax.jit(F.pow_inv)(dev_p[2])))[0])
    rx, ry = ref.point_affine(ref_p)
    assert (x, y) == (rx, ry)


def test_point_add_double_matches_oracle():
    for _ in range(5):
        p, q = rand_point(), rand_point()
        assert_same_point(jadd_pt(to_dev_point(p), to_dev_point(q)), ref.point_add(p, q))
        assert_same_point(jdbl_pt(to_dev_point(p)), ref.point_double(p))
    # identity edge cases (unified formulas must handle them)
    ident = tuple(jnp.asarray(v)[None, :] for v in (
        F.limbs_from_int(0), F.limbs_from_int(1), F.limbs_from_int(1), F.limbs_from_int(0)))
    p = rand_point()
    assert_same_point(jadd_pt(to_dev_point(p), ident), p)
    assert_same_point(jadd_pt(ident, ident), ref.IDENTITY)


def _sign_many(n, msg_fn):
    items = []
    for i in range(n):
        seed = bytes([i]) * 32
        pk = ref.public_from_seed(seed)
        msg = msg_fn(i)
        items.append((msg, pk, ref.sign(seed, msg)))
    return items


@pytest.fixture(scope="module")
def verifier():
    return BatchVerifier(min_device_batch=0)  # force the kernel path


def test_batch_all_valid(verifier):
    items = _sign_many(5, lambda i: b"msg-%d" % i)
    out = verifier.verify(*map(list, zip(*items)))
    assert out.tolist() == [True] * 5


def test_batch_mixed_invalid(verifier):
    items = _sign_many(8, lambda i: b"payload-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))
    expected = [True] * 8
    # corrupt signature R
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]; expected[1] = False
    # corrupt s half
    sigs[2] = sigs[2][:40] + bytes([sigs[2][40] ^ 0x80]) + sigs[2][41:]; expected[2] = False
    # wrong message
    msgs[3] = b"tampered"; expected[3] = False
    # wrong key
    pks[4] = ref.public_from_seed(b"\xaa" * 32); expected[4] = False
    # non-canonical s (s + L)
    s_int = int.from_bytes(sigs[5][32:], "little") + ref.L
    sigs[5] = sigs[5][:32] + s_int.to_bytes(32, "little"); expected[5] = False
    # undecompressable pubkey (y >= p encodes no point)
    pks[6] = (ref.P + 1).to_bytes(32, "little"); expected[6] = False
    out = verifier.verify(msgs, pks, sigs)
    assert out.tolist() == expected
    # agreement with the oracle on every item
    for got, (m, pk, sig) in zip(out.tolist(), zip(msgs, pks, sigs)):
        assert got == ref.verify(sig, pk, m)


def test_rfc_vectors_on_device(verifier):
    vecs = [
        ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60", ""),
        ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb", "72"),
        ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7", "af82"),
    ]
    msgs, pks, sigs = [], [], []
    for seed_hex, msg_hex in vecs:
        seed, msg = bytes.fromhex(seed_hex), bytes.fromhex(msg_hex)
        msgs.append(msg)
        pks.append(ref.public_from_seed(seed))
        sigs.append(ref.sign(seed, msg))
    assert verifier.verify(msgs, pks, sigs).tolist() == [True] * 3


def test_qc_shape_shared_message(verifier):
    """The QC-verify shape: many signers, one digest."""
    digest = hashlib.sha512(b"block").digest()[:32]
    msgs, pks, sigs = [], [], []
    for i in range(7):
        seed = bytes([0x40 + i]) * 32
        msgs.append(digest)
        pks.append(ref.public_from_seed(seed))
        sigs.append(ref.sign(seed, digest))
    assert verifier.verify(msgs, pks, sigs).all()
    sigs[3] = sigs[3][:10] + b"\x00" + sigs[3][11:]
    out = verifier.verify(msgs, pks, sigs)
    assert out.tolist() == [True, True, True, False, True, True, True]


def test_committee_precompute_cache(verifier):
    pks = [ref.public_from_seed(bytes([i]) * 32) for i in range(4)]
    verifier.precompute(pks)
    assert all(pk in verifier._point_cache for pk in pks)


def test_warmup_leaves_the_key_table_at_its_production_size():
    """A padded wave brings the pad claim's key.  The warm-up puts it in
    the point cache before it compiles anything, so the staged table,
    whose row count is a shape of the device-side gather, does not grow
    under the first production wave and the gather does not compile
    again at every pad shape inside that wave's deadline."""
    from hotstuff_tpu.crypto.async_service import make_pad_claim
    from hotstuff_tpu.tpu import ed25519 as device

    v = BatchVerifier(min_device_batch=0)
    committee = _sign_many(7, lambda i: b"a proposal")
    v.precompute([pk for _, pk, _ in committee])
    v.warmup(batch=8)
    rows = len(v._tables[1])
    compiled = device._gather_rows._cache_size()
    _, digest, pk, sig = make_pad_claim()
    msgs, pks, sigs = map(list, zip(*committee))
    # the wave as the service pads it: real claims, then pad claims
    out = v.verify_device(msgs + [digest] * 9, pks + [pk] * 9, sigs + [sig] * 9)
    assert out.all()
    assert len(v._tables[1]) == rows
    assert device._gather_rows._cache_size() == compiled


def test_pallas_dsm_parity_interpret():
    """The Pallas double-scalar-mult kernel (tpu/pallas_dsm.py) must agree
    with the XLA path bit-for-bit.  Runs in interpreter mode so the
    parity check works on the CPU test mesh; on-device coverage comes
    from the benchmark and the TPU rig."""
    from hotstuff_tpu.tpu import pallas_dsm
    from hotstuff_tpu.tpu.ed25519 import _bytes_to_windows_msb

    B = pallas_dsm.LANE_TILE  # minimum lane-aligned batch
    s_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    k_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    s_win = jnp.asarray(_bytes_to_windows_msb(s_rows).T)
    k_win = jnp.asarray(_bytes_to_windows_msb(k_rows).T)
    pts = [rand_point() for _ in range(B)]
    a_point = tuple(
        jnp.asarray(np.stack([curve.point_to_limbs(p)[c] for p in pts]))
        for c in range(4)
    )

    x_out = curve.dual_scalar_mult(s_win, k_win, a_point)
    p_out = pallas_dsm.dual_scalar_mult(s_win, k_win, a_point, interpret=True)
    canon = jax.jit(F.canonical)
    # X, Y, Z only: the pallas kernel's need_t schedule leaves T
    # uncomputed (compressed_equals never reads it)
    for xla, pal in list(zip(x_out, p_out))[:3]:
        assert (np.asarray(canon(xla)) == np.asarray(canon(pal))).all()


def test_pallas_fused_epilogue_parity_interpret():
    """The in-kernel compressed-equality epilogue (limb-major ports of
    _chain/_strict/canonical/pow_inv) against the XLA field ops: encode
    the XLA scan's outputs host-side, corrupt the sign on some lanes and
    the y encoding on others, and check the fused unsplit kernel's
    verdict lane-by-lane."""
    import jax.numpy as jnp

    from hotstuff_tpu.tpu import pallas_dsm
    from hotstuff_tpu.tpu.ed25519 import _bytes_to_windows_msb

    B = pallas_dsm.LANE_TILE
    s_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    k_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    s_win = jnp.asarray(_bytes_to_windows_msb(s_rows).T)
    k_win = jnp.asarray(_bytes_to_windows_msb(k_rows).T)
    pts = [rand_point() for _ in range(B)]
    a_point = tuple(
        jnp.asarray(np.stack([curve.point_to_limbs(p)[c] for p in pts]))
        for c in range(4)
    )

    # the true compressed encodings, via the XLA path
    X, Y, Z, _ = curve.dual_scalar_mult(s_win, k_win, a_point)
    zinv = jax.jit(F.pow_inv)(Z)
    y_can = np.asarray(jax.jit(F.canonical)(F.mul(Y, zinv)))
    x_can = np.asarray(jax.jit(F.canonical)(F.mul(X, zinv)))
    r_y = y_can.copy()
    r_sign = (x_can[:, 0] & 1).astype(np.int32)
    expect = np.ones(B, bool)
    r_sign[:8] ^= 1  # wrong sign bit
    r_y[8:16, 0] ^= 1  # wrong y encoding
    expect[:16] = False

    ok = np.asarray(
        pallas_dsm.verify_compressed(
            s_win,
            k_win,
            a_point,
            jnp.asarray(r_y),
            jnp.asarray(r_sign),
            interpret=True,
        )
    )
    assert ok.tolist() == expect.tolist()


def test_donate_buffers_env_gate(monkeypatch):
    """HOTSTUFF_DONATE forces buffer donation on/off; unset defers to
    the backend platform (accelerators donate, CPU jax would warn)."""
    monkeypatch.setenv("HOTSTUFF_DONATE", "0")
    assert not BatchVerifier(min_device_batch=0).donate_buffers
    monkeypatch.setenv("HOTSTUFF_DONATE", "1")
    assert BatchVerifier(min_device_batch=0).donate_buffers
    monkeypatch.delenv("HOTSTUFF_DONATE")
    v = BatchVerifier(min_device_batch=0)
    assert v.donate_buffers == (jax.default_backend() in ("tpu", "gpu"))


def test_donated_dispatch_verdict_parity(monkeypatch):
    """With donation forced on, staging buffers are consumed per wave —
    and because verify() restages every wave, back-to-back waves of
    different shapes (and a repeat of the first) keep exact verdict
    parity.  The committee gather source (args 0-3) is NOT donated, so
    the epoch-static key tables survive every wave."""
    monkeypatch.setenv("HOTSTUFF_DONATE", "1")
    v = BatchVerifier(min_device_batch=0)
    assert v.donate_buffers
    items = _sign_many(6, lambda i: b"donate-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))
    sigs[2] = bytes([sigs[2][0] ^ 1]) + sigs[2][1:]
    expected = [True, True, False, True, True, True]
    assert v.verify(msgs, pks, sigs).tolist() == expected
    # a different wave shape in between...
    items2 = _sign_many(3, lambda i: b"other-%d" % i)
    assert v.verify(*map(list, zip(*items2))).tolist() == [True] * 3
    # ...then the first wave again: donation corrupted nothing cached
    assert v.verify(msgs, pks, sigs).tolist() == expected


def test_challenge_hash_memo():
    """The per-(sig, pk, msg) challenge-hash memo serves repeated rows
    (pad claims, re-verified certificates) without re-hashing — and
    never changes a verdict."""
    v = BatchVerifier(min_device_batch=0)
    items = _sign_many(4, lambda i: b"memo-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))
    assert v.verify(msgs, pks, sigs).all()
    assert len(v._challenge_memo) == 4
    assert v.verify(msgs, pks, sigs).all()  # served from the memo


def test_stage_routing_thresholds():
    """stage() contract after the split-kernel deletion: every batch
    goes through prepare() to _run_kernel (overridden by the
    mesh-sharded subclass); use_pallas only changes which kernel
    _run_kernel dispatches."""
    items = _sign_many(3, lambda i: b"route-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))

    for use_pallas in (True, False):
        v = BatchVerifier(min_device_batch=0, use_pallas=use_pallas)
        kernel, arrays, valid = v.stage(msgs, pks, sigs)
        assert kernel == v._run_kernel
        assert valid.all() and len(arrays) == 8
