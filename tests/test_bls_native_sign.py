"""Native BLS signing (``hs_bls_sign`` in native/bls_pairing.cpp, bridged
by ``crypto/bls/native.py`` ``sign``) against the pure-Python oracle
``BlsSecretKey.sign``: the same 48 bytes for every key and message,
signatures that verify on both sides, refusals of keys that are not a
scalar in [1, r), and ``BlsSigningService``'s choice between the two
with its ``native_signs`` counter."""

from __future__ import annotations

import hashlib
import json
import os
import random
import subprocess
import sys

import pytest

from hotstuff_tpu.crypto.bls import BlsSecretKey, BlsSignature
from hotstuff_tpu.crypto.bls.curve import H1, G1Point, hash_to_g1
from hotstuff_tpu.crypto.bls.fields import P, R, X
from hotstuff_tpu.crypto.bls.service import BlsSigningService
from hotstuff_tpu.telemetry.blsstats import BLS_COUNTS, FIELDS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DST = b"HOTSTUFF_TPU_BLS_G1"
PAIRS = 64


@pytest.fixture(scope="module")
def native():
    try:
        from hotstuff_tpu.crypto.bls import native
    except ImportError:
        pytest.skip("native BLS library unavailable")
    return native


def tries(message: bytes) -> int:
    """Counters hash_to_g1's try-and-increment takes to find x on the
    curve (written out here, so the test does not trust the map)."""
    counter = 0
    while True:
        h = hashlib.sha256(DST + counter.to_bytes(4, "big") + message).digest()
        x = int.from_bytes(h + hashlib.sha256(b"x2" + h).digest()[:16], "big") % P
        y2 = (x**3 + 4) % P
        if pow(y2, (P + 1) // 4, P) ** 2 % P == y2:
            return counter + 1
        counter += 1


def pair(i: int) -> tuple[int, bytes]:
    """The i-th seeded (scalar, message): even i a message whose hash
    needs more than one try, odd i any message of a length consensus or
    a proof of possession signs, or none."""
    rng = random.Random(0xB15_5164 + i)
    scalar = rng.randrange(1, R)
    if i % 2:
        return scalar, rng.randbytes(rng.choice((0, 1, 32, 33, 96, 128)))
    while True:
        message = rng.randbytes(32)
        if tries(message) > 1:
            return scalar, message


def le32(scalar: int) -> bytes:
    return scalar.to_bytes(32, "little")


@pytest.mark.parametrize("i", range(PAIRS))
def test_native_sign_is_the_python_signature_and_verifies(native, i):
    scalar, message = pair(i)
    sk = BlsSecretKey(scalar)
    sig = native.sign(message, le32(scalar))
    assert sig == sk.sign(message).to_bytes()
    pk = sk.public_key()
    assert pk.verify(message, BlsSignature.from_bytes(sig))
    assert native.verify_one(message, pk.to_bytes(), sig)
    assert not native.verify_one(message + b"?", pk.to_bytes(), sig)


def test_the_seeded_pairs_hold_retried_hashes():
    assert sum(tries(pair(i)[1]) > 1 for i in range(PAIRS)) >= PAIRS // 2
    assert max(tries(pair(i)[1]) for i in range(0, PAIRS, 2)) >= 3


@pytest.mark.parametrize("scalar", [1, 2, R - 1], ids=["one", "two", "r-1"])
def test_native_sign_at_the_scalars_edges(native, scalar):
    for message in (b"", b"edge", bytes(32)):
        sig = native.sign(message, le32(scalar))
        assert sig == BlsSecretKey(scalar).sign(message).to_bytes()
    if scalar == R - 1:  # (r-1)*H(m) = -H(m): x equal, the sign bit flipped
        one = native.sign(b"edge", le32(1))
        assert native.sign(b"edge", le32(scalar)) == (
            bytes([one[0] ^ 0x20]) + one[1:]
        )


# The native sign clears H(m)'s cofactor with h_eff = 1 - x and folds the
# rest of h1 = (1 - x)*c into the key: sk*[h1]B = [sk*c mod r]([1 - x]B).
# It then splits k' = sk*c mod r as k2*x^2 + k1 (GLV, x^2 the eigenvalue
# of -phi on G1).  Written out here, not read from the library.
H_EFF = 1 - X
C = H_EFF // 3
X2 = X * X
# k' = k2*x^2 + k1 on the split's edges: k2 = 0 (1, 2, and x^2 - 1, the
# largest k1), k1 = 0 (x^2, and r - 1 = (x^2 - 1)*x^2, the largest k2),
# both 1, the largest 128-bit k', and r - x^2 = (x^2 - 2)*x^2 + 1
FOLDED_EDGES = {
    "one": 1, "two": 2, "x2-1": X2 - 1, "x2": X2, "x2+1": X2 + 1,
    "2^128-1": 2**128 - 1, "r-x2": R - X2, "r-1": R - 1,
}  # fmt: skip


def hash_base(message: bytes) -> G1Point:
    """hash_to_g1's point before its cofactor is cleared (the map as
    ``tries`` writes it out)."""
    counter = tries(message) - 1
    h = hashlib.sha256(DST + counter.to_bytes(4, "big") + message).digest()
    x = int.from_bytes(h + hashlib.sha256(b"x2" + h).digest()[:16], "big") % P
    y = pow((x**3 + 4) % P, (P + 1) // 4, P)
    return G1Point(x, min(y, P - y))


def test_h1_is_h_eff_times_c():
    assert H_EFF == 0xD201000000010001 and C == 0x460055555555AAAB
    assert H_EFF * C == H1 and H_EFF % 3 == 0
    assert X2 < 2**128 and R < X2 * X2  # so k1 and k2 are below 2^128


@pytest.mark.parametrize("kprime", FOLDED_EDGES.values(), ids=FOLDED_EDGES.keys())
def test_native_sign_where_the_folded_key_is_on_the_splits_edges(native, kprime):
    scalar = kprime * pow(C, -1, R) % R
    assert scalar * C % R == kprime
    sk = BlsSecretKey(scalar)
    pk = sk.public_key().to_bytes()
    for message in (b"", b"edge", bytes(32), pair(0)[1]):
        sig = native.sign(message, le32(scalar))
        assert sig == sk.sign(message).to_bytes()
        assert native.verify_one(message, pk, sig)


@pytest.mark.parametrize("kprime", FOLDED_EDGES.values(), ids=FOLDED_EDGES.keys())
def test_the_glv_split_in_curve_terms(kprime):
    """[k']P = [k1]P + [k2](-phi(P)) on G1, phi(x, y) = (beta*x, y) with
    beta the cube root of unity whose eigenvalue is -x^2."""
    g = G1Point.generator()
    beta = next(
        b for b in (pow(w, (P - 1) // 3, P) for w in range(2, 10))
        if b != 1 and G1Point(b * g.x, g.y) == -g._mul_raw(X2)
    )  # fmt: skip
    k2, k1 = divmod(kprime, X2)
    assert k1 < 2**128 and k2 < 2**128
    p = g._mul_raw(0xB15_5164)
    minus_phi = G1Point(beta * p.x, -p.y)
    assert p._mul_raw(k1) + minus_phi._mul_raw(k2) == p._mul_raw(kprime)


@pytest.mark.parametrize("i", range(8))
def test_h_eff_clears_the_cofactor_and_the_rest_folds_into_the_key(native, i):
    """For raw hash bases B, none of them in G1: [1 - x]B is in G1,
    and [sk*c mod r]([1 - x]B) is [sk]([h1]B), the signature's point."""
    scalar, message = pair(2 * i)  # a message whose hash was retried
    base = hash_base(message)
    assert native.hash_base_many([message]) == (
        base.x.to_bytes(48, "big") + base.y.to_bytes(48, "big")
    )
    assert base.mul_by_cofactor() == hash_to_g1(message)
    cleared = base._mul_raw(H_EFF)
    assert cleared.in_subgroup() and not cleared.inf
    assert cleared.mul(scalar * C % R) == base.mul_by_cofactor().mul(scalar)


def test_the_hash_bases_lie_outside_g1():
    assert not any(hash_base(pair(2 * i)[1]).in_subgroup() for i in range(8))


@pytest.mark.parametrize(
    "key",
    [bytes(32), le32(R), le32(R + 1), b"\xff" * 32, le32(1)[:31], le32(1) + b"\0"],
    ids=["zero", "r", "r+1", "2^256-1", "31-bytes", "33-bytes"],
)
def test_native_sign_refuses_what_is_no_scalar_below_r(native, key):
    assert native.sign(b"refused", key) is None


def counts() -> dict:
    return dict(BLS_COUNTS.counts)


def test_the_service_signs_natively_and_counts_it(native):
    scalar, message = pair(0)
    svc = BlsSigningService(BlsSecretKey(scalar))
    assert svc._native_sign is native.sign
    before = counts()
    sig = svc.sign_sync(message)
    after = counts()
    assert sig.to_bytes() == BlsSecretKey(scalar).sign(message).to_bytes()
    assert after["signs"] - before["signs"] == 1
    assert after["native_signs"] - before["native_signs"] == 1
    assert "native_signs" in FIELDS and "native_signs=" in BLS_COUNTS.line()
    svc.shutdown()
    assert svc._sk is None and svc._sk_le32 is None


def test_the_service_without_the_library_signs_in_python(monkeypatch):
    import hotstuff_tpu.crypto.bls as package

    # the import the service makes now fails, as without the library
    monkeypatch.delattr(package, "native", raising=False)
    monkeypatch.setitem(sys.modules, "hotstuff_tpu.crypto.bls.native", None)
    scalar, message = pair(2)
    svc = BlsSigningService(BlsSecretKey(scalar))
    assert svc._native_sign is None
    before = counts()
    sig = svc.sign_sync(message)
    after = counts()
    assert sig.to_bytes() == BlsSecretKey(scalar).sign(message).to_bytes()
    assert after["signs"] - before["signs"] == 1
    assert after["native_signs"] == before["native_signs"]


def test_a_refused_native_call_falls_back_to_python(native):
    scalar, message = pair(3)
    svc = BlsSigningService(BlsSecretKey(scalar))
    svc._native_sign = lambda _message, _key: None
    before = counts()
    sig = svc.sign_sync(message)
    assert sig.to_bytes() == BlsSecretKey(scalar).sign(message).to_bytes()
    assert counts()["native_signs"] == before["native_signs"]


def test_native_off_signs_the_same_bytes_in_python(native):
    """``HOTSTUFF_BLS_NATIVE=0`` in a process of its own: no library,
    the signature made in Python, the same bytes as the native call's."""
    scalar, message = pair(4)
    code = (
        "import json, sys\n"
        "from hotstuff_tpu.crypto.bls.service import BlsSigningService\n"
        "from hotstuff_tpu.telemetry.blsstats import BLS_COUNTS\n"
        f"svc = BlsSigningService(bytes.fromhex({scalar.to_bytes(32, 'big').hex()!r}))\n"
        f"sig = svc.sign_sync(bytes.fromhex({message.hex()!r}))\n"
        "print(json.dumps([svc._native_sign is None, sig.to_bytes().hex(),"
        " BLS_COUNTS.counts]))\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "HOTSTUFF_BLS_NATIVE": "0"}, timeout=120,
    )  # fmt: skip
    assert done.returncode == 0, done.stderr[-2000:]
    no_native, sig, line = json.loads(done.stdout.strip().splitlines()[-1])
    assert no_native
    assert bytes.fromhex(sig) == native.sign(message, le32(scalar))
    assert (line["signs"], line["native_signs"]) == (1, 0)


def test_a_proof_of_possession_is_made_natively_with_the_same_bytes(
    native, monkeypatch
):
    """``bls_pop`` signs ``_POP_DST`` and the key in one native call, the
    bytes ``prove_possession`` makes in pure Python, and makes them so
    without the library too; the proof verifies either way."""
    import hotstuff_tpu.crypto.bls as package
    from hotstuff_tpu.crypto.bls import prove_possession, verify_possession
    from hotstuff_tpu.crypto.scheme import bls_keygen, bls_pop

    pk, secret = bls_keygen(b"p" * 32, 7)
    sk = BlsSecretKey(int.from_bytes(secret, "big"))
    assert pk.to_bytes() == sk.public_key().to_bytes()
    expected = prove_possession(sk).to_bytes()
    assert bls_pop(secret) == expected
    monkeypatch.delattr(package, "native", raising=False)
    monkeypatch.setitem(sys.modules, "hotstuff_tpu.crypto.bls.native", None)
    assert bls_pop(secret) == expected
    assert verify_possession(sk.public_key(), BlsSignature.from_bytes(expected))
