"""ctypes bridge to the native batched Ed25519 verifier
(native/ed25519_batch.cpp).

This is the dalek-parity CPU batch path: the reference's
``Signature::verify_batch`` (crypto/src/lib.rs:213-226) delegates to
ed25519-dalek's random-linear-combination batch verification; this
bridge exposes the same equation implemented in C++ (Pippenger
multiscalar over the 51-bit-limb field).  On the pre-chip rig it
verified a 256-vote QC ~3.7x faster than the per-signature OpenSSL
loop — it is the production fast path for QC-shaped verification
(``CpuVerifier.verify_shared_msg``) and the CPU baseline a device
verifier has to beat.

The ctypes call releases the GIL for the whole batch, so off-thread
callers (AsyncVerifyService workers) overlap it with event-loop work.

Failure semantics: the batch equation is all-or-nothing — callers
needing per-item attribution fall back to the per-signature loop on a
False.  Acceptance is cofactored (dalek-batch parity); singles remain
on OpenSSL's cofactorless path, the same mix the reference ships.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

_LIB_NAME = "libhs_ed25519.so"

# Measured crossover on the dev rig where the batch equation beats the
# per-signature OpenSSL loop.  With the Straus small-batch path in the
# native MSM (r5) the batch wins from n=2 up (n=2: 0.13 vs 0.24 ms;
# n=4: 0.21 vs 0.49; n=11: 0.50 vs 1.46; n=256: 8.5 vs 31.4).  n=1
# stays on OpenSSL: a lone signature gets the cofactorless
# verify_strict-style semantics the reference uses for singles.  The
# single source of truth — the verifier backend and the async router
# both import it.
NATIVE_BATCH_MIN = 2


def _native_dir() -> str:
    return os.path.join(
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
        "native",
    )


def _load_lib() -> ctypes.CDLL:
    if os.environ.get("HOTSTUFF_ED25519_NATIVE") == "0":
        raise ImportError("native batch verify disabled via env")
    path = os.path.join(_native_dir(), "build", _LIB_NAME)
    try:
        # ALWAYS run make for the SPECIFIC target (a no-op when the .so
        # is current): loading only-if-absent left a stale prebuilt .so
        # in place across source updates, and a library missing a newly
        # added symbol crashes at bind time below.  A compile failure in
        # an unrelated native TU must not disable this fast path (the
        # Makefile's mktemp+rename keeps concurrent builders from
        # exposing a partially-written .so).
        subprocess.run(
            ["make", "-C", _native_dir(), f"build/{_LIB_NAME}"],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as e:
        if not os.path.exists(path):
            raise ImportError(f"cannot build {_LIB_NAME}: {e}") from e
        # no toolchain but a prebuilt .so exists: try it — the symbol
        # binding below rejects it if it is too old
    try:
        lib = ctypes.CDLL(path)
        lib.hs_ed25519_batch_verify.restype = ctypes.c_int
        lib.hs_ed25519_batch_verify.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_int,
        ]
        lib.hs_ed25519_precompute.restype = ctypes.c_int
        lib.hs_ed25519_precompute.argtypes = [ctypes.c_char_p, ctypes.c_uint32]
    except (OSError, AttributeError) as e:
        # corrupt/truncated/ABI-mismatched/stale .so (AttributeError =
        # missing symbol): degrade to the OpenSSL path instead of
        # letting the error escape into QC verify
        raise ImportError(f"cannot load {_LIB_NAME}: {e}") from e
    return lib


# None = never tried; False = tried and failed (cached — a missing
# compiler must not re-spawn `make` on every QC verify); CDLL = loaded.
_lib: ctypes.CDLL | bool | None = None


def available() -> bool:
    global _lib
    if _lib is None:
        try:
            _lib = _load_lib()
        except ImportError as e:
            import logging

            logging.getLogger(__name__).info(
                "native batch verifier unavailable (%s); using the "
                "per-signature CPU path",
                e,
            )
            _lib = False
    return _lib is not False


def batch_verify(
    msgs: bytes, msg_len: int, pks: bytes, sigs: bytes, n: int, shared: bool
) -> bool:
    """True iff ALL n signatures satisfy the batch equation.

    ``msgs`` is n*msg_len contiguous bytes (or msg_len bytes when
    ``shared``); ``pks`` n*32; ``sigs`` n*64.  Malformed encodings
    (non-canonical points/scalars) verify False.
    """
    if n == 0:
        return True
    assert _lib is not None and _lib is not False, "call available() first"
    # Buffer-length validation BEFORE crossing into C: a short component
    # (e.g. a 48-byte BLS-sized signature smuggled into an ed25519
    # batch) must be an invalid-signature verdict, not an out-of-bounds
    # read.
    if (
        len(msgs) != (msg_len if shared else n * msg_len)
        or len(pks) != n * 32
        or len(sigs) != n * 64
    ):
        return False
    return (
        _lib.hs_ed25519_batch_verify(
            msgs, msg_len, pks, sigs, n, 1 if shared else 0
        )
        == 1
    )


def precompute(pubkeys: list[bytes]) -> int:
    """Build the native committee-key tables (epoch setup): each 32-byte
    key gets its decompressed negated point + Straus window table cached
    in the C library, so every later batch only pays point work for the
    per-signature R points.  Returns the number of keys cached (wrong-
    size or off-curve keys are skipped — they fail at verify time)."""
    if not available():
        return 0
    pks = b"".join(pk for pk in pubkeys if len(pk) == 32)
    n = len(pks) // 32
    if n == 0:
        return 0
    return int(_lib.hs_ed25519_precompute(pks, n))


def verify_one(msg: bytes, pk: bytes, sig: bytes) -> bool:
    """Single-signature verify through ``hs_ed25519_verify_one``.
    Cofactored acceptance (batch-equation semantics) — callers that need
    the cofactorless single-signature path keep OpenSSL; this is the
    fast fallback when ``cryptography`` is absent and the alternative is
    the pure-Python ladder (~30x slower)."""
    if len(pk) != 32 or len(sig) != 64 or not available():
        return False
    return int(_lib.hs_ed25519_verify_one(msg, len(msg), pk, sig)) == 1


def batch_verify_shared(msg: bytes, votes) -> bool:
    """All (pk_bytes, sig_bytes) pairs over one message (QC shape)."""
    n = len(votes)
    if n == 0:
        return True
    pks = b"".join(pk for pk, _ in votes)
    sigs = b"".join(sig for _, sig in votes)
    return batch_verify(msg, len(msg), pks, sigs, n, shared=True)


def batch_verify_columns(
    dig_addr: int, pks_addr: int, sigs_addr: int, n: int
) -> bool:
    """Batch verify straight from native arena column addresses
    (wave_pack.cpp staging memory) — the zero-copy CPU route: no
    ``b"".join`` flatten, no bytes materialization.  The addresses come
    from ``WavePacker.arena_info`` and stay valid until the arena is
    recycled; the caller owns that lifetime."""
    if n == 0:
        return True
    assert _lib is not None and _lib is not False, "call available() first"
    return (
        _lib.hs_ed25519_batch_verify(
            ctypes.cast(dig_addr, ctypes.c_char_p),
            32,
            ctypes.cast(pks_addr, ctypes.c_char_p),
            ctypes.cast(sigs_addr, ctypes.c_char_p),
            n,
            0,
        )
        == 1
    )


# ---------------------------------------------------------------------------
# Wave-pack arena bindings (native/wave_pack.cpp, ISSUE 20)
#
# The wp_* ABI ships in libhs_transport.so (same dlopen handle as the
# reactor's ht_* surface) — votes parsed at the reactor read path land
# in bucket-shaped staging arenas that the async verify service adopts
# as NumPy frombuffer views instead of flattening Python claim tuples.
# ---------------------------------------------------------------------------

_TRANSPORT_LIB = "libhs_transport.so"

# None = never tried; False = unavailable (cached); CDLL = loaded
_wp_lib: ctypes.CDLL | bool | None = None


def _load_wave_lib() -> ctypes.CDLL:
    path = os.path.join(_native_dir(), "build", _TRANSPORT_LIB)
    try:
        subprocess.run(
            ["make", "-C", _native_dir(), f"build/{_TRANSPORT_LIB}"],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as e:
        if not os.path.exists(path):
            raise ImportError(f"cannot build {_TRANSPORT_LIB}: {e}") from e
    try:
        lib = ctypes.CDLL(path)
        lib.wp_create.restype = ctypes.c_void_p
        lib.wp_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.wp_destroy.argtypes = [ctypes.c_void_p]
        lib.wp_set_pad.restype = ctypes.c_int
        lib.wp_set_pad.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        lib.wp_probe_vote.restype = ctypes.c_int
        lib.wp_probe_vote.argtypes = [ctypes.c_char_p, ctypes.c_long]
        lib.wp_pack_vote.restype = ctypes.c_long
        lib.wp_pack_vote.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_char_p,
        ]
        lib.wp_count.restype = ctypes.c_long
        lib.wp_count.argtypes = [ctypes.c_void_p]
        lib.wp_seal.restype = ctypes.c_long
        lib.wp_seal.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.wp_arena_info.restype = ctypes.c_int
        lib.wp_arena_info.argtypes = [
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.wp_recycle.restype = ctypes.c_int
        lib.wp_recycle.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.wp_discard.restype = ctypes.c_int
        lib.wp_discard.argtypes = [ctypes.c_void_p]
        lib.wp_counters.restype = ctypes.c_int
        lib.wp_counters.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int,
        ]
        lib.wp_parse_producer.restype = ctypes.c_long
        lib.wp_parse_producer.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
        ]
    except (OSError, AttributeError) as e:
        raise ImportError(f"cannot load {_TRANSPORT_LIB}: {e}") from e
    return lib


def wave_pack_available() -> bool:
    global _wp_lib
    if _wp_lib is None:
        try:
            _wp_lib = _load_wave_lib()
        except ImportError as e:
            import logging

            logging.getLogger(__name__).info(
                "native wave packer unavailable (%s); ingest stays on the "
                "Python flatten path",
                e,
            )
            _wp_lib = False
    return _wp_lib is not False


MAX_PRODUCER_BATCH = 512


def probe_vote(frame: bytes) -> bool:
    """Stateless Decoder-parity accept/reject for a vote frame (the
    differential fuzz harness drives this against decode_message)."""
    assert _wp_lib is not None and _wp_lib is not False
    return _wp_lib.wp_probe_vote(frame, len(frame)) == 1


def parse_producer(frame: bytes):
    """Decoder-parity producer-v2 parse: ``(digests, spans)`` where
    ``digests`` is the packed 32B digest column and ``spans`` is a list
    of ``(offset, length)`` body windows into ``frame`` — or ``None``
    for any frame the Python Decoder rejects."""
    assert _wp_lib is not None and _wp_lib is not False
    digs = ctypes.create_string_buffer(MAX_PRODUCER_BATCH * 32)
    spans = (ctypes.c_uint64 * (MAX_PRODUCER_BATCH * 2))()
    n = _wp_lib.wp_parse_producer(frame, len(frame), digs, spans)
    if n < 0:
        return None
    return (
        digs.raw[: n * 32],
        [(spans[2 * i], spans[2 * i + 1]) for i in range(n)],
    )


class WavePacker:
    """Owner of one native arena ring.  ``pack_vote`` runs on the event
    loop (reactor drain path); ``recycle`` runs on verifier slot threads
    once the adopted views are consumed — the native side serializes
    both under one mutex."""

    def __init__(self, capacity: int, ring_depth: int = 4):
        if not wave_pack_available():
            raise ImportError("wave packer unavailable")
        assert _wp_lib is not None and _wp_lib is not False
        self._lib = _wp_lib
        self._h = self._lib.wp_create(capacity, ring_depth)
        if not self._h:
            raise MemoryError("wp_create failed")
        self.capacity = capacity
        self.ring_depth = ring_depth
        self._digest_out = ctypes.create_string_buffer(32)

    def close(self) -> None:
        if self._h:
            self._lib.wp_destroy(self._h)
            self._h = None

    def set_pad(self, digest: bytes, pk: bytes, sig: bytes) -> bool:
        return self._lib.wp_set_pad(self._h, digest, pk, sig) == 0

    def pack_vote(self, frame: bytes):
        """``(row_slot, claim_digest32)`` on success, else the negative
        native error code (int): -1 malformed frame, -2 open arena
        full, -3 no pad installed."""
        slot = self._lib.wp_pack_vote(
            self._h, frame, len(frame), self._digest_out
        )
        if slot < 0:
            return int(slot)
        return slot, self._digest_out.raw

    def count(self) -> int:
        return int(self._lib.wp_count(self._h))

    def seal(self, n_take: int) -> int | None:
        """Seal the open arena at ``n_take`` rows (surplus rows carry
        over to the next arena).  Returns the sealed arena index."""
        idx = self._lib.wp_seal(self._h, n_take)
        return None if idx < 0 else int(idx)

    def arena_info(self, arena: int):
        """``(dig_addr, pk_addr, sig_addr, rows, capacity)`` of a sealed
        arena — feed the addresses to ``column_view`` / NumPy."""
        out = (ctypes.c_uint64 * 5)()
        if self._lib.wp_arena_info(self._h, arena, out) != 0:
            return None
        return (
            int(out[0]),
            int(out[1]),
            int(out[2]),
            int(out[3]),
            int(out[4]),
        )

    def recycle(self, arena: int) -> bool:
        return self._lib.wp_recycle(self._h, arena) == 0

    def discard(self) -> bool:
        return self._lib.wp_discard(self._h) == 0

    def counters(self) -> dict:
        out = (ctypes.c_uint64 * 7)()
        n = self._lib.wp_counters(self._h, out, 7)
        names = (
            "packed",
            "reject",
            "full",
            "seal",
            "discard",
            "recycle",
            "moved",
        )
        return {names[i]: int(out[i]) for i in range(n)}


def column_view(addr: int, nbytes: int):
    """Writable buffer over ``nbytes`` of native arena memory at
    ``addr`` (buffer-protocol object — ``np.frombuffer`` accepts it
    directly).  Valid only until the owning arena is recycled."""
    return (ctypes.c_uint8 * nbytes).from_address(addr)
