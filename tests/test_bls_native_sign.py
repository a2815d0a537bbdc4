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
from hotstuff_tpu.crypto.bls.fields import P, R
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
