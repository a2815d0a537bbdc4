"""SignatureService — the actor holding the node's secret key.

Parity target: reference ``SignatureService`` (``crypto/src/lib.rs:232-257``):
callers submit a digest and await the signature through a oneshot. This is
the trait boundary the TPU backend slots behind (BASELINE.json north star):
``VerifierBackend`` decides where *verification* work runs (CPU loop vs
batched TPU kernel); signing stays on CPU (one ~25 µs OpenSSL sign per
vote/block is never the bottleneck — QC verify is).
"""

from __future__ import annotations

import time
from typing import Iterable, Protocol

from ..telemetry import spans as _spans
from .digest import Digest
from .keys import PublicKey, SecretKey
from .signature import CryptoError, Signature


class VerifierBackend(Protocol):
    """Where batched verification work executes.

    Beyond the three methods, the async dispatch pipeline
    (crypto/async_service.py) consults OPTIONAL capability attributes
    via ``getattr``; a backend advertises only what it supports, and
    absence means the default shown:

    - ``name = "?"`` — backend label for stats/telemetry tags;
    - ``supports_flat_batch = False`` — ``eval_claims_sync`` may
      collapse a whole claim wave into one native batch equation;
    - ``prefers_aggregate = False`` — shared-message claims must route
      through ``verify_shared_msg`` (BLS: one pairing per claim);
    - ``async_kind`` (unset) — advertises the off-loop coalescing claim
      path; one shared service per (event loop, kind);
    - ``always_offload = False`` — worker-thread offload is always
      worthwhile (the backend releases the GIL), skip cost-model routing;
    - ``device_ready = True`` — the device kernel is warm; the service
      never routes to a backend that would cold-compile mid-consensus;
    - ``dispatch_deadline_s = 0.1`` — floor for the per-dispatch
      deadline (raised adaptively from the dispatch EWMA);
    - ``device_counters()`` (unset: zeros) — cumulative ``(h2d,
      calls)``: host arrays handed to the device and jitted calls made,
      printed on the service's stats line after ``chunks=``
      (tpu/ed25519.BatchVerifier and parallel/mesh.ShardedBatchVerifier
      count one of each a wave: the staging buffer, the entry that
      decomposes, gathers and verifies);
    - ``supports_wave_padding = False`` — device-routed waves may be
      pre-padded to fixed bucket shapes (``HOTSTUFF_WAVE_BUCKETS``)
      with always-valid filler claims so every dispatch hits a warm
      jitted callable; only backends whose per-claim verdicts are
      independent of the other claims in the batch may opt in (the
      ed25519 device verifiers do; aggregate-preferring backends and
      synthetic test hosts must not);
    - ``wave_bucket_shapes`` (unset) — the backend's own preferred
      bucket ladder for fixed-shape padding, overriding the canonical
      default (but not an explicit ``HOTSTUFF_WAVE_BUCKETS``): the
      mesh-sharded verifier advertises its pad-grid entries here so
      every padded wave is a mesh-multiple pre-compiled kernel shape
      (ISSUE 7); device HOSTS forward it as None until the device
      materializes.
    """

    def verify_one(self, digest: Digest, pk: PublicKey, sig: Signature) -> bool: ...

    def verify_shared_msg(
        self, digest: Digest, votes: list[tuple[PublicKey, Signature]]
    ) -> bool:
        """All signatures over one shared digest (QC verify shape)."""
        ...

    def verify_many(
        self,
        digests: list[bytes],
        pks: list[bytes],
        sigs: list[bytes],
        aggregate_ok: bool = False,
    ) -> list[bool]:
        """Per-item validity over distinct messages (TC verify / eviction
        shape).

        ``aggregate_ok=True`` permits backends to use AGGREGATE
        acceptance within same-digest groups — per-entry results may
        then be certified only collectively (entries that individually
        fail but cancel in the sum pass).  That is sound ONLY for
        certificate verification whose trust base already covers
        aggregation (TC.verify: PoP-checked keys, stake rules run
        first — the same argument as QC aggregation).  Callers that
        make PER-ENTRY decisions (the aggregator's eviction/suspect
        logic) must leave it False."""
        ...


from .native_ed25519 import NATIVE_BATCH_MIN


class CpuVerifier:
    """Default backend: OpenSSL per-signature verification, with the
    native dalek-parity batch equation (crypto/native_ed25519.py) as
    the fast path for large same-digest batches — the QC-verify shape,
    reference crypto/src/lib.rs:213-226."""

    name = "cpu"
    # eval_claims_sync may collapse a whole claim wave into one native
    # batch equation (all-or-nothing, per-item attribution on failure)
    supports_flat_batch = True

    def verify_one(self, digest: Digest, pk: PublicKey, sig: Signature) -> bool:
        try:
            sig.verify(digest, pk)
            return True
        except CryptoError:
            return False

    def precompute(self, pubkeys: list[bytes]) -> None:
        """Warm the native committee-key tables (node boot / epoch
        setup) so QC-shaped batches only pay point decompression for
        the per-signature R points.  No-op without the native lib."""
        from . import native_ed25519

        native_ed25519.precompute(pubkeys)

    def verify_shared_msg(
        self, digest: Digest, votes: list[tuple[PublicKey, Signature]]
    ) -> bool:
        with _spans.span("host.verify"):
            if len(votes) >= NATIVE_BATCH_MIN:
                from . import native_ed25519

                if native_ed25519.available():
                    # cofactored batch acceptance — dalek-batch parity;
                    # the certificate verdict is all-or-nothing, same as
                    # the reference's QC::verify
                    return native_ed25519.batch_verify_shared(
                        digest.to_bytes(),
                        [
                            (pk.to_bytes(), sig.to_bytes())
                            for pk, sig in votes
                        ],
                    )
            try:
                Signature.verify_batch(digest, votes)
                return True
            except CryptoError:
                return False

    def verify_many(
        self,
        digests: list[bytes],
        pks: list[bytes],
        sigs: list[bytes],
        aggregate_ok: bool = False,
    ) -> list[bool]:
        from .signature import batch_verify_arrays

        with _spans.span("host.verify"):
            n = len(digests)
            if aggregate_ok and n >= NATIVE_BATCH_MIN:
                # Certificate-shaped call (TC verify): the all-pass
                # verdict may be established collectively.  One batch
                # equation replaces n verifies; on a failure fall
                # through to the loop for per-item attribution.
                from . import native_ed25519

                if (
                    native_ed25519.available()
                    and all(len(d) == Digest.SIZE for d in digests)
                    and native_ed25519.batch_verify(
                        b"".join(digests),
                        Digest.SIZE,
                        b"".join(pks),
                        b"".join(sigs),
                        n,
                        shared=False,
                    )
                ):
                    return [True] * n
            return batch_verify_arrays(digests, pks, sigs)


class VerifyWork:
    """One node's verification work as its verifier saw it: calls,
    signatures and wall time — the protocol's dominant CPU cost, which
    the committee-scaling decomposition (benchmark/scaling.py) reads
    from the ``Telemetry snapshot:`` document."""

    __slots__ = ("verify_calls", "verify_sigs", "verify_wall_s", "started")

    def __init__(self):
        self.verify_calls = 0
        self.verify_sigs = 0
        self.verify_wall_s = 0.0
        self.started = time.monotonic()

    def to_json(self) -> dict:
        return {
            "elapsed_s": round(time.monotonic() - self.started, 3),
            "verify_calls": self.verify_calls,
            "verify_sigs": self.verify_sigs,
            "verify_wall_ms": round(self.verify_wall_s * 1e3, 3),
        }


class CountingVerifier:
    """Delegating VerifierBackend that accounts calls/signatures/wall
    time into a VerifyWork."""

    def __init__(self, inner, work: VerifyWork):
        self.inner = inner
        self.work = work
        self.name = getattr(inner, "name", "counted")

    def _timed(self, n_sigs: int, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        self.work.verify_wall_s += time.perf_counter() - t0
        self.work.verify_calls += 1
        self.work.verify_sigs += n_sigs
        return out

    def verify_one(self, digest, pk, sig) -> bool:
        return self._timed(1, self.inner.verify_one, digest, pk, sig)

    def verify_shared_msg(self, digest, votes) -> bool:
        return self._timed(
            len(votes), self.inner.verify_shared_msg, digest, votes
        )

    def verify_many(self, digests, pks, sigs, aggregate_ok: bool = False):
        def call(d, p, s):
            return self.inner.verify_many(d, p, s, aggregate_ok=aggregate_ok)

        return self._timed(len(digests), call, digests, pks, sigs)

    def __getattr__(self, item):
        # precompute/warmup/etc. pass through untimed
        return getattr(self.inner, item)


class SignatureService:
    """The service owning the node's secret key.

    The reference implements this as an actor (a channel of
    (digest, oneshot) pairs consumed by one task, crypto/src/lib.rs:
    232-257) because tokio tasks run on many threads.  Under asyncio's
    single thread the queue hop would cost two task switches (~45 us
    each, profiled) around a ~20 us OpenSSL sign, so ``request_signature``
    signs inline; the async signature is kept as the API boundary.  The
    parsed private key is constructed once and reused; ``shutdown()``
    drops the key and wipes the secret, after which requests raise.
    """

    def __init__(self, secret: SecretKey):
        self._secret = secret
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PrivateKey,
            )

            self._key: object | None = Ed25519PrivateKey.from_private_bytes(
                secret.seed
            )
        except ImportError:  # pure-Python fallback keeps the same surface
            from .ed25519_ref import sign as _ref_sign

            seed = secret.seed

            class _RefKey:
                __slots__ = ()

                @staticmethod
                def sign(msg: bytes) -> bytes:
                    return _ref_sign(seed, msg)

            self._key = _RefKey()
        self._closed = False

    async def request_signature(self, digest: Digest) -> Signature:
        return self.sign_sync(digest)

    def sign_sync(self, digest: Digest) -> Signature:
        """Synchronous signing for tests/fixtures (reference ``new_from_key``
        test constructors, consensus/src/tests/common.rs:48-114)."""
        if self._closed or self._key is None:
            raise RuntimeError("SignatureService is shut down")
        with _spans.span("core.sign"):
            return Signature(self._key.sign(digest.to_bytes()))  # type: ignore[attr-defined]

    def shutdown(self) -> None:
        self._closed = True
        self._key = None
        self._secret.wipe()
