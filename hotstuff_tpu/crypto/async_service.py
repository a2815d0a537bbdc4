"""Asynchronous, coalescing signature verification.

This is the off-critical-path dispatch layer for the TPU verifier
(VERDICT r3 item 1): the consensus core collects every signature check a
message burst needs as *claims*, submits them here, and awaits ONE
verdict — while the actual device dispatch runs on a worker thread so
the event loop keeps processing votes, proposals and payload ingest.
Measured rationale (round 4):

- a TPU dispatch costs the dispatch latency, flat in batch size — so the
  only sane unit of dispatch is "everything currently pending";
- concurrent dispatches pipeline (16 in flight ≈ the cost of 1), so a
  single in-flight batch with arrivals gathering for the next one loses
  nothing;
- ``jax.block_until_ready`` releases the GIL (measured: a spinning
  thread keeps ~91% of its throughput during device verifies), so a
  worker thread parks on the device for free — while the host-side
  OpenSSL path holds the GIL (~83% occupancy measured), which is why
  the CPU fallback runs inline instead of pretending a thread helps.

Claims (the burst-level accumulate-then-dispatch unit):

- ``("one", digest_bytes, pk_bytes, sig_bytes)`` — a single signature
  over its own message (votes, block author sigs, TC entries);
- ``("shared", digest_bytes, ((pk_bytes, sig_bytes), ...))`` — many
  signatures over ONE message (the QC shape; also grouped timeout
  floods).  Verdict is all-or-nothing.

Backends that prefer aggregate verification of shared claims (BLS: one
pairing equality per claim instead of one per signature) advertise
``prefers_aggregate = True``; everything else is flattened into one
``verify_many`` batch — one device dispatch for the whole wave.

Adaptive routing: the service tracks an EWMA of device dispatch wall
time and routes each batch to the device only when that estimate beats
the measured CPU cost (n_sigs x ~140 us).  When the dispatch path
degrades the service degrades to the CPU path instead of stalling
consensus — and keeps probing the device so it recovers with it (the
reference's graceful best-effort philosophy at the FFI boundary,
SURVEY.md §7 "hard parts").

Pipelined dispatch (ISSUE 5): up to ``pipeline_depth`` device waves may
be in flight at once (default 2, ``HOTSTUFF_VERIFY_PIPELINE`` /
``--verify-pipeline``).  While wave N parks on the device, wave N+1
flattens, pads and transfers on a second worker thread, so the fixed
dispatch latency amortizes across in-flight waves instead of gating
the committee per wave (the "16 in flight ≈ the cost of 1" measurement
above is exactly why this works).  Each wave lands through its own
completion future — out-of-order completion resolves each batch's own
waiters, and a failed wave poisons only its own futures.  The cost
model learns the marginal device cost: with waves already in flight,
an extra wave rides the occupied dispatch path, so the EWMA is discounted by
``PIPELINE_MARGINAL_COST``.  At full occupancy a device-preferred wave
QUEUES for a slot (bounded by the earliest in-flight deadline) rather
than spilling to the CPU; an OVERDUE in-flight wave routes everything
to the CPU, preserving the anti-stall behavior of the old
single-in-flight gate.

Straight-line dispatch (ISSUE 6): device dispatches run on a
dedicated dispatch loop — ``pipeline_depth`` long-lived slot threads
over one queue — instead of a per-service ``ThreadPoolExecutor`` hop.
Each slot thread owns its thread-local staging scratch in the device
backend (tpu/ed25519.py pools scratch per thread), so the slots ARE a
ring of preallocated staging buffers: wave N parks on the device from
one slot while wave N+1 stages into the next slot's buffers.  Waves
routed to a padding-capable backend (``supports_wave_padding``) are
pre-padded to fixed bucket shapes (``HOTSTUFF_WAVE_BUCKETS``, default
16/64/256/1024) with always-valid pad claims so ``route.decide ->
dispatch`` hits a pre-compiled jitted callable every time, and an
optional round window (``HOTSTUFF_COALESCE_WINDOW_MS``) holds the wave
open so QC and TC claims from the same round merge into ONE device
dispatch with a claim-table fanout on readback.  The device backend
donates its staging buffers across waves (``donate_argnums`` in
tpu/ed25519.py) so XLA reuses device allocations instead of
re-allocating per wave.
"""

from __future__ import annotations

import asyncio
import atexit
import itertools
import logging
import queue
import threading
import time

from ..telemetry import spans as _spans
from .digest import DIGEST_SIZE
from .native_ed25519 import NATIVE_BATCH_MIN

log = logging.getLogger(__name__)

# Measured single-signature CPU verify cost on this class of host
# (OpenSSL Ed25519 via `cryptography`, scripts in round 4: ~123-142 us).
# Only used as the device-vs-CPU routing threshold — an order-of-
# magnitude estimate is enough.
CPU_US_PER_SIG = 130.0

# Native-batch cost model: per-sig cost ~ asymptote + fixed/n (the
# Pippenger bucket cost amortizes with n).  Fit to the r5 measurements
# (~108 us/sig at 11, ~54 at 32, ~46 at 128, ~36 at 256).
CPU_BATCH_US_PER_SIG = 45.0
CPU_BATCH_FIXED_US = 700.0


def cpu_batch_estimate_s(n_sigs: int) -> float:
    """Estimated batched-CPU wall seconds for an n_sigs wave."""
    return n_sigs * (CPU_BATCH_US_PER_SIG + CPU_BATCH_FIXED_US / n_sigs) * 1e-6

# EWMA smoothing for device dispatch wall time.
_EWMA_ALPHA = 0.3

# When the device EWMA says "lose", still probe the device this often so
# a recovered dispatch path is noticed (seconds).
_PROBE_INTERVAL_S = 3.0

# Default dispatch pipeline depth: waves in flight on the device at
# once.  2 gives staging/execute overlap without queueing enough work
# behind a dispatch stall to hurt (the deadline + overdue routing below
# bound the damage to one deadline regardless of depth).
DEFAULT_PIPELINE_DEPTH = 2

# Marginal cost factor for a device dispatch when waves are already in
# flight: concurrent dispatches pipeline (measured: 16 in flight ≈ the
# cost of 1), so the route cost model discounts the EWMA for every wave
# after the first instead of charging each a full round trip.
PIPELINE_MARGINAL_COST = 0.25


def pipeline_depth_from_env() -> int:
    """Dispatch pipeline depth from HOTSTUFF_VERIFY_PIPELINE (min 1)."""
    import os

    raw = os.environ.get("HOTSTUFF_VERIFY_PIPELINE", "")
    try:
        depth = int(raw)
    except ValueError:
        depth = DEFAULT_PIPELINE_DEPTH
    return max(1, depth)


# Fixed wave shapes (ISSUE 6): device-routed waves on padding-capable
# backends are pre-padded with always-valid pad claims to the smallest
# of these bucket sizes, so every dispatch hits a pre-compiled jitted
# callable instead of a shape-polymorphic retrace.  Aligned with the
# tpu/ed25519.py PAD_SIZES grid.
DEFAULT_WAVE_BUCKETS: tuple[int, ...] = (16, 64, 256, 1024)


def wave_buckets_from_env() -> tuple[int, ...]:
    """Wave bucket sizes from HOTSTUFF_WAVE_BUCKETS (comma-separated,
    e.g. "16,64,256,1024"); "0"/"off" disables fixed-shape padding
    (returns an empty tuple).  Unset or unparsable -> the default."""
    import os

    raw = os.environ.get("HOTSTUFF_WAVE_BUCKETS")
    if raw is None:
        return DEFAULT_WAVE_BUCKETS
    raw = raw.strip().lower()
    if raw in ("", "0", "off", "none", "no", "false"):
        return ()
    try:
        sizes = sorted({int(tok) for tok in raw.split(",") if tok.strip()})
    except ValueError:
        return DEFAULT_WAVE_BUCKETS
    return tuple(s for s in sizes if s > 0)


def resolve_wave_buckets(backend) -> tuple[int, ...]:
    """The bucket ladder for ``backend`` (ISSUE 7): an explicit
    ``HOTSTUFF_WAVE_BUCKETS`` always wins; otherwise a backend that
    advertises ``wave_bucket_shapes`` (the mesh verifier's mesh-multiple
    grid entries, so every padded wave IS a pre-compiled kernel shape
    with equal per-device slices) gets its own shapes; everything else
    gets the canonical default ladder."""
    import os

    if "HOTSTUFF_WAVE_BUCKETS" in os.environ:
        return wave_buckets_from_env()
    shapes = getattr(backend, "wave_bucket_shapes", None)
    if shapes:
        return tuple(sorted({int(b) for b in shapes if int(b) > 0}))
    return DEFAULT_WAVE_BUCKETS


def coalesce_window_s_from_env() -> float:
    """QC+TC coalescing window from HOTSTUFF_COALESCE_WINDOW_MS, in
    SECONDS.  Default 0: coalescing stays yield-based (two event-loop
    passes), adding zero latency; a positive window holds each wave
    open so both certificate kinds from one round share a dispatch."""
    import os

    raw = os.environ.get("HOTSTUFF_COALESCE_WINDOW_MS", "")
    try:
        ms = float(raw)
    except ValueError:
        ms = 0.0
    return max(0.0, ms) * 1e-3


def claim_sig_count(c) -> int:
    """Signatures a claim carries: 1 for "one", the vote-list length for
    "shared", the SIGNER count for "agg" (whose c[2] is the 48-byte
    aggregate-signature blob — len(c[2]) would miscount it as 48)."""
    if c[0] == "one":
        return 1
    if c[0] == "agg":
        return len(c[3])
    return len(c[2])


def flatten_claims(claims: list) -> tuple[list, list, list, list]:
    """Claims -> (digests, pks, sigs, spans); spans[i] = (start, end)
    slice of the flat arrays belonging to claims[i].

    This is the Python fallback for transports without the native
    zero-copy ingest plane (ISSUE 20) — kept allocation-lean: the
    column lists are preallocated at their final length in one sizing
    pass and filled by index, so the hot loop never grows a list or
    re-reads ``len`` per claim (measured as the ``flatten`` p50 in
    ``benchmark profile``)."""
    n_claims = len(claims)
    spans: list = [None] * n_claims
    total = 0
    for i, claim in enumerate(claims):
        k = 1 if claim[0] == "one" else len(claim[2])
        spans[i] = (total, total + k)
        total += k
    digests: list = [None] * total
    pks: list = [None] * total
    sigs: list = [None] * total
    pos = 0
    for claim in claims:
        if claim[0] == "one":
            digests[pos] = claim[1]
            pks[pos] = claim[2]
            sigs[pos] = claim[3]
            pos += 1
        else:  # "shared"
            d = claim[1]
            for pk, sig in claim[2]:
                digests[pos] = d
                pks[pos] = pk
                sigs[pos] = sig
                pos += 1
    return digests, pks, sigs, spans


def eval_claims_sync(backend, claims: list) -> list[bool]:
    """Synchronous claim evaluation on ``backend`` (the inline path and
    the worker-thread body).  Shared claims go through the backend's
    aggregate check when it prefers one (BLS); otherwise everything
    flattens into a single ``verify_many`` batch."""
    if getattr(backend, "prefers_aggregate", False):
        with _spans.span("agg.verify"):
            from .digest import Digest
            from .keys import PublicKey
            from .signature import Signature

            out: list[bool] = []
            singles: list[tuple[int, tuple]] = []
            for claim in claims:
                if claim[0] == "shared":
                    votes = [
                        (PublicKey(pk), Signature(sig))
                        for pk, sig in claim[2]
                    ]
                    # zero signatures prove nothing (flatten path below)
                    out.append(
                        bool(votes)
                        and bool(
                            backend.verify_shared_msg(Digest(claim[1]), votes)
                        )
                    )
                elif claim[0] == "agg":
                    # compact certificate: pre-aggregated signature +
                    # bitmap-resolved signer keys — ONE pairing however
                    # large the committee.  claim[2] is the agg-sig
                    # BYTES (not a vote list): it must never reach the
                    # flatten/verify_many shapes.
                    fn = getattr(backend, "verify_aggregate_msg", None)
                    out.append(
                        fn is not None
                        and bool(
                            fn(Digest(claim[1]), list(claim[3]), claim[2])
                        )
                    )
                else:
                    singles.append((len(out), claim))
                    out.append(False)  # placeholder
            if singles:
                ok = backend.verify_many(
                    [c[1] for _, c in singles],
                    [c[2] for _, c in singles],
                    [c[3] for _, c in singles],
                )
                for (pos, _), valid in zip(singles, ok):
                    out[pos] = bool(valid)
            return out

    if any(c[0] == "agg" for c in claims):
        # non-aggregating backend (ed25519) handed a compact
        # certificate: resolve each "agg" claim directly (False when the
        # backend has no aggregate verify — the wire layer already
        # rejects compact forms for such committees, this is the
        # loopback/defence-in-depth path) and recurse on the rest.
        from .digest import Digest

        fn = getattr(backend, "verify_aggregate_msg", None)
        out = []
        rest = [c for c in claims if c[0] != "agg"]
        rest_verdicts = iter(
            eval_claims_sync(backend, rest) if rest else ()
        )
        for c in claims:
            if c[0] == "agg":
                out.append(
                    fn is not None
                    and bool(fn(Digest(c[1]), list(c[3]), c[2]))
                )
            else:
                out.append(next(rest_verdicts))
        return out

    with _spans.span("flatten"):
        digests, pks, sigs, spans = flatten_claims(claims)
    if not digests:
        # every claim here is an empty "shared" (zero members): a
        # certificate with no signatures proves nothing — vacuous truth
        # (all() over an empty span) would verify a votes=[] forgery
        return [False] * len(claims)
    # Wave-level fast path (CPU backend): ONE dalek-parity batch
    # equation over the whole flattened wave — in the common all-valid
    # case this replaces len(digests) OpenSSL verifies with a single
    # Pippenger multiscalar (measured 2-3.5x).  Sound because every
    # claim's verdict here is all(span): a passing batch implies every
    # span passes.  On a failing batch fall through to per-item
    # attribution (the adversary pays for that path, not us).
    if (
        len(digests) >= NATIVE_BATCH_MIN
        and getattr(backend, "supports_flat_batch", False)
        and all(len(d) == DIGEST_SIZE for d in digests)
    ):
        from . import native_ed25519

        with _spans.span("host.verify"):
            fast_ok = native_ed25519.available() and native_ed25519.batch_verify(
                b"".join(digests),
                DIGEST_SIZE,
                b"".join(pks),
                b"".join(sigs),
                len(digests),
                shared=False,
            )
        if fast_ok:
            return [e > s for s, e in spans]
    ok = backend.verify_many(digests, pks, sigs)
    return [all(ok[s:e]) if e > s else False for s, e in spans]


# ---------------------------------------------------------------------------
# Zero-copy wire -> device ingest (ISSUE 20)
#
# With the native transport, vote frames are parsed and packed IN C++
# (native/wave_pack.cpp) straight into bucket-shaped staging arenas at
# the reactor's read path.  When a dispatch wave's claim stream turns
# out to be exactly the packed arena prefix (receive order == claim
# submission order on a single-node transport), the service ADOPTS the
# arena — flatten/prepare become NumPy frombuffer views over memory the
# native parser already filled — instead of walking Python claim
# objects.  Adoption is an exact byte-level match; ANY divergence
# (deduped duplicates, stake/lookahead-dropped votes, mixed QC+vote
# waves, co-located multi-node dedup) falls back to flatten_claims.
# The arena is an accelerator, never a correctness dependency.
# ---------------------------------------------------------------------------

#: wire tag of a vote frame (consensus/wire.py TAG_VOTE).  Hardcoded —
#: importing consensus.wire here would cycle (wire imports crypto);
#: tests/test_wire_fuzz.py asserts this constant against the live one.
INGEST_TAG_VOTE = 1

#: arenas in the native ring: pipeline depth 2, a probe, and headroom
#: before pack falls back
DEFAULT_INGEST_RING_DEPTH = 6


_pad_claim_cached: tuple | None = None


def make_pad_claim() -> tuple:
    """The deterministic filler claim for fixed-shape padding: one VALID
    self-contained ed25519 signature over a reserved digest.  Shared by
    the service's Python packing (_pack_wave) and the native ingest
    arenas (wp_set_pad pre-fills every arena row with it), so an
    adopted wave's pad rows are byte-identical to Python-padded ones."""
    global _pad_claim_cached
    if _pad_claim_cached is None:
        from .digest import Digest
        from .keys import generate_keypair
        from .signature import Signature

        pk, sk = generate_keypair(b"\xa5" * 32, 0xFFFF)
        digest = Digest.of(b"hotstuff_tpu wave pad claim v1")
        sig = Signature.new(digest, sk)
        _pad_claim_cached = (
            "one", digest.to_bytes(), pk.to_bytes(), sig.to_bytes()
        )
    return _pad_claim_cached


class AdoptedWave:
    """A sealed native staging arena adopted as one verification wave:
    ``n`` real claim rows followed by valid pad rows up to ``rows`` (the
    wave bucket).  The column views die when ``release`` recycles the
    arena — every consumer releases in a ``finally``."""

    __slots__ = (
        "ingest", "arena", "n", "rows",
        "dig", "pk", "sig", "dig_addr", "pk_addr", "sig_addr",
        "_released",
    )

    def __init__(self, ingest, arena: int, n: int, rows: int, info):
        from .native_ed25519 import column_view

        self.ingest = ingest
        self.arena = arena
        self.n = n
        self.rows = rows
        self.dig_addr, self.pk_addr, self.sig_addr = info[0], info[1], info[2]
        self.dig = column_view(self.dig_addr, rows * 32)
        self.pk = column_view(self.pk_addr, rows * 32)
        self.sig = column_view(self.sig_addr, rows * 64)
        self._released = False

    def release(self) -> None:
        """Recycle the arena (idempotent; runs on verifier slot threads
        — the native mutex serializes with event-loop packing)."""
        if not self._released:
            self._released = True
            self.ingest.packer.recycle(self.arena)


class ZeroCopyIngest:
    """Process-global zero-copy ingest plane: owns the native arena
    ring and the Python-side key mirror that proves adoption safety.

    ``note_vote_frame`` (event loop, receiver path) packs each vote's
    digest/pk/sig columns natively and mirrors the claim KEY (the exact
    bytes ``Vote.claim()`` would produce).  ``try_adopt`` (event loop,
    dispatcher) hands the arena over iff the wave's claims are exactly
    the packed key prefix — verdicts bind positionally downstream, so
    the match must be exact, and the mirror makes it checkable without
    decoding anything twice."""

    def __init__(
        self, capacity: int | None = None, ring_depth: int | None = None
    ):
        from .native_ed25519 import WavePacker

        # the largest canonical wave bucket by default, so every
        # bucket-shaped wave is a prefix view of one arena
        self.packer = WavePacker(
            capacity or DEFAULT_WAVE_BUCKETS[-1],
            ring_depth or DEFAULT_INGEST_RING_DEPTH,
        )
        pad = make_pad_claim()
        if not self.packer.set_pad(pad[1], pad[2], pad[3]):
            raise RuntimeError("wave packer pad install failed")
        self._keys: list[tuple] = []
        self.packed_votes = 0
        self.zero_copy_waves = 0
        self.fallback_waves = 0

    @property
    def active(self) -> bool:
        """Any packed votes pending adoption?  The dispatcher skips the
        adoption attempt entirely when nothing was packed (sim/asyncio
        transports, non-vote traffic)."""
        return bool(self._keys)

    def note_vote_frame(self, frame: bytes) -> bool:
        r = self.packer.pack_vote(frame)
        if isinstance(r, int):
            if r == -2:
                # open arena full: the pack stream outran adoption (an
                # idle service, or votes that never became claims) —
                # resync rather than wedge with a full arena forever
                self._resync()
            return False
        _slot, digest = r
        # the claim key mirrors Vote.claim(): (digest, author pk, sig) —
        # pk/sig slices at the fixed ed25519 vote-frame offsets
        self._keys.append((digest, frame[45:77], frame[81:145]))
        self.packed_votes += 1
        return True

    def try_adopt(self, claims: list, buckets) -> AdoptedWave | None:
        """Adopt the packed prefix as ``claims``' wave, or None.

        On a mismatch that OVERLAPS the packed stream (a packed vote is
        in this wave but not at its packed position: dedup, a dropped
        vote, a mixed QC+vote wave) the open arena is discarded — those
        rows can never line up again.  A wave fully DISJOINT from the
        packed keys (pure QC/proposal wave between vote bursts) leaves
        the arena untouched for the next wave."""
        keys = self._keys
        n = len(claims)
        if n <= len(keys):
            for i in range(n):
                c = claims[i]
                if c[0] != "one" or (c[1], c[2], c[3]) != keys[i]:
                    break
            else:
                rows = next((b for b in buckets if b >= n), None)
                if rows is None or rows > self.packer.capacity:
                    rows = n
                arena = self.packer.seal(n)
                if arena is None:
                    self._resync()
                    return None
                info = self.packer.arena_info(arena)
                if info is None:  # unreachable right after seal; be safe
                    self.packer.recycle(arena)
                    self._resync()
                    return None
                del keys[:n]
                self.zero_copy_waves += 1
                return AdoptedWave(self, arena, n, rows, info)
        key_set = set(keys)
        if any(
            c[0] == "one" and (c[1], c[2], c[3]) in key_set for c in claims
        ):
            self._resync()
            self.fallback_waves += 1
        return None

    def _resync(self) -> None:
        self.packer.discard()
        self._keys.clear()

    def counters(self) -> dict:
        out = self.packer.counters()
        out["zero_copy_waves"] = self.zero_copy_waves
        out["fallback_waves"] = self.fallback_waves
        return out


#: None = never tried; False = unavailable (cached); else the
#: live ZeroCopyIngest
_zero_copy: "ZeroCopyIngest | bool | None" = None


def zero_copy_ingest() -> "ZeroCopyIngest | None":
    """The process-global ingest plane, created on first use by a
    receiver; None when the native packer is unavailable (no toolchain
    — cached, never retried per frame)."""
    global _zero_copy
    if _zero_copy is None:
        from . import native_ed25519

        created: ZeroCopyIngest | bool = False
        if native_ed25519.wave_pack_available():
            try:
                created = ZeroCopyIngest()
            except Exception as e:  # noqa: BLE001 — ingest must
                # degrade to the Python path, never break receive
                log.info("zero-copy ingest unavailable: %s", e)
        _zero_copy = created
    return _zero_copy if type(_zero_copy) is ZeroCopyIngest else None


def zero_copy_ingest_if_active() -> "ZeroCopyIngest | None":
    """The ingest plane IF a receiver already created it — the
    dispatcher-side accessor: never triggers a native build from the
    verify path."""
    return _zero_copy if type(_zero_copy) is ZeroCopyIngest else None


def ingest_note_frame(frame: bytes) -> None:
    """Receiver-side hook: feed one raw inbound frame to the zero-copy
    plane just before handler dispatch.  Only vote frames are packed;
    anything else is a cheap tag test.  Never raises into the receive
    loop."""
    if not frame or frame[0] != INGEST_TAG_VOTE:
        return
    ing = zero_copy_ingest()
    if ing is not None:
        try:
            ing.note_vote_frame(frame)
        except Exception:  # noqa: BLE001 — a packer bug must not kill
            # the connection; the wave simply falls back to Python
            log.exception("zero-copy vote pack failed")


def eval_claims_arena(backend, wave: AdoptedWave, claims: list) -> list[bool]:
    """Evaluate an adopted zero-copy wave: the arena columns ARE the
    staging arrays — no flatten, no per-claim bytes.  Device backends
    verify through ``verify_packed`` (frombuffer views over the columns
    feed the jitted bucket callable at the pre-padded bucket shape);
    CPU backends run ONE native batch equation straight from the column
    addresses.  Any miss (failing batch equation -> per-item
    attribution, backend without a packed path) falls back to
    ``eval_claims_sync`` on the claim list.  Always releases the
    arena."""
    try:
        n = wave.n
        fn = getattr(backend, "verify_packed", None)
        if fn is not None:
            out = fn(wave.dig, wave.pk, wave.sig, wave.rows)
            return [bool(v) for v in out[:n]]
        from . import native_ed25519

        if (
            n >= NATIVE_BATCH_MIN
            and getattr(backend, "supports_flat_batch", False)
            and native_ed25519.available()
        ):
            with _spans.span("host.verify"):
                fast_ok = native_ed25519.batch_verify_columns(
                    wave.dig_addr, wave.pk_addr, wave.sig_addr, n
                )
            if fast_ok:
                return [True] * n
        return eval_claims_sync(backend, claims)
    finally:
        wave.release()


#: every live _DispatchLoop, for interpreter-exit shutdown (satellite:
#: no leaked thread keeps the interpreter from exiting — slot threads
#: are daemons AND get an explicit sentinel at atexit)
_live_dispatch_loops: "set[_DispatchLoop]" = set()


@atexit.register
def _shutdown_dispatch_loops() -> None:
    for dl in list(_live_dispatch_loops):
        dl.close()


class _DispatchLoop:
    """The dedicated dispatch loop (ISSUE 6): ``depth`` long-lived slot
    threads over one queue, replacing the per-service
    ``ThreadPoolExecutor`` hop (thread-pool bookkeeping, per-submit
    ``concurrent.futures`` machinery, idle-timeout respawn).  Each slot
    thread keeps its own thread-local staging scratch in the device
    backend, so a slot is one entry of a preallocated staging-buffer
    ring: with ``depth`` slots, up to ``depth`` waves stage/execute
    concurrently and never allocate fresh host buffers.

    Completion callbacks run ON the slot thread — callers hop back to
    their event loop with ``call_soon_threadsafe``.  Threads are lazy
    (first ``submit`` starts them), daemonic, and shut down cleanly on
    ``close()`` and at interpreter exit."""

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._threads: list[threading.Thread] = []
        self._closed = False
        _live_dispatch_loops.add(self)

    def submit(self, fn, on_done) -> None:
        """Queue ``fn`` for the next free slot thread;
        ``on_done(result, exc)`` runs on that thread when it finishes."""
        if self._closed:
            raise RuntimeError("dispatch loop is closed")
        if not self._threads:
            for i in range(self.depth):
                t = threading.Thread(
                    target=self._worker,
                    name=f"verify-slot-{i}",
                    daemon=True,
                )
                t.start()
                self._threads.append(t)
        self._q.put((fn, on_done))

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, on_done = item
            try:
                result, exc = fn(), None
            except BaseException as e:  # noqa: BLE001 — delivered to the
                result, exc = None, e  # waiter, never raised in the slot
            try:
                on_done(result, exc)
            except Exception:  # noqa: BLE001 — a delivery failure must
                log.exception("verify dispatch delivery failed")

    def close(self, wait: bool = False) -> None:
        """Stop the slot threads after their current job (idempotent)."""
        if self._closed:
            return
        self._closed = True
        _live_dispatch_loops.discard(self)
        for _ in range(len(self._threads)):
            self._q.put(None)
        if wait:
            for t in self._threads:
                t.join(timeout=1.0)
        self._threads = []


class AsyncVerifyService:
    """Coalesces claim batches and (for device backends) dispatches them
    from a worker thread.

    One service instance per (event loop, device backend): in-process
    committees share the backend object (node.LazyDeviceVerifier keeps a
    per-kind singleton), so every node's claims coalesce into the same
    dispatch stream — one dispatch covers the whole committee's
    wave.  CPU backends get an inline service (``device=False``): claims
    evaluate synchronously at the submit point, zero added latency.
    """

    _registry: dict[tuple, tuple] = {}  # (loop id, kind) -> (loop, service)
    _serial = 0  # distinguishes the services' cumulative stat lines
    # one counter a process: a wave's serial joins its spans in a trace
    # (``wave=<serial>``), whichever service of the process made it
    _wave_serials = itertools.count(1)

    def __init__(
        self, backend, device: bool = False, pipeline_depth: int | None = None
    ):
        AsyncVerifyService._serial += 1
        # stable tag for the scraped stats line: kind#pid.serial —
        # cumulative counters from different service instances must be
        # separable in MERGED logs: the serial separates the services
        # of one process (one a loop and kind), the pid separates
        # processes (every node process restarts the class counter at
        # 1, and the parser sums the last line per tag)
        import os

        kind = getattr(backend, "async_kind", None) or getattr(
            backend, "name", "cpu"
        )
        self._backend_kind = kind
        self._stats_tag = f"{kind}#{os.getpid()}.{AsyncVerifyService._serial}"
        # For inline services ``backend`` is the VerifierBackend itself.
        # For device services it is the HOST (node.LazyDeviceVerifier):
        # ``host.device_ready`` gates routing (never materialize jax or
        # cold-compile mid-consensus), ``host.async_backend`` is the
        # forced-device dispatch view, ``host.cpu_backend`` the fallback.
        self.backend = backend
        self.device = device
        # HOTSTUFF_NO_CLAIM_DEDUP=1 (see for_backend): a wave keeps one
        # entry for every submitted claim, not one for every distinct one
        self._dedup = not os.environ.get("HOTSTUFF_NO_CLAIM_DEDUP")
        self._pending: list[tuple[list, asyncio.Future]] = []
        # profiling: perf_counter_ns stamps of device-path submissions in
        # the current coalescing window (empty unless HOTSTUFF_PROFILE)
        self._arrivals: list[int] = []
        self._task: asyncio.Task | None = None
        self._dispatch: _DispatchLoop | None = None
        # fixed-shape wave padding + round coalescing (ISSUE 6).
        # Packing only applies when the backend advertises
        # supports_wave_padding (real device verifiers): synthetic test
        # hosts and CPU backends see exactly the claims submitted.
        # Bucket shapes resolve dynamically (see the wave_buckets
        # property): the mesh backend's shapes only exist once the
        # device host materializes it at warmup.
        self.coalesce_window_s = coalesce_window_s_from_env()
        self._pad_claim: tuple | None = None
        self.packed_waves = 0
        self.pad_sigs = 0
        # adaptive routing state
        self._device_ewma_s: float | None = None
        self._last_probe = 0.0
        # dispatch pipeline (ISSUE 5): wave serial -> monotonic deadline
        # stamp for every device dispatch currently in flight.  Routing
        # reads occupancy (len) and overdue-ness; landers and probe
        # done-callbacks remove their wave and signal _slot_free.
        self.pipeline_depth = (
            max(1, int(pipeline_depth))
            if pipeline_depth
            else pipeline_depth_from_env()
        )
        self._inflight: dict[int, float] = {}
        # overdue waves that have had their one yield of the loop
        # before the traffic was routed round them (_route_device)
        self._graced: set[int] = set()
        self._wave_serial = 0
        self._slot_free: asyncio.Event | None = None
        self._landers: set[asyncio.Task] = set()
        self.dispatches = 0
        self.device_dispatches = 0
        self.cpu_dispatches = 0
        self.probe_dispatches = 0
        # mesh route label (ISSUE 7): device waves dispatched into a
        # mesh-sharded backend count separately so committee runs can
        # tell sharded dispatches from single-device ones in the scaling
        # SUMMARY's route column (device_dispatches stays the total)
        self.mesh_dispatches = 0
        self._device_route_label = (
            "mesh" if ("sharded" in str(kind) or "mesh" in str(kind))
            else "device"
        )
        self.device_sigs = 0
        self.cpu_sigs = 0
        # what the cores handed in (before collection), the rows handed
        # to the device (pads included) and the backend calls that took
        # them: submitted over evaluated is the fan-out the dedup
        # removes, device_sigs over lanes the buckets' occupancy
        self.submitted_sigs = 0
        self.lanes = 0
        self.chunks = 0
        # compact-certificate ("agg") claims and the signer count they
        # covered — the one-pairing route (ISSUE 9); surfaced on the
        # stats line for benchmark/logs.py's agg columns
        self.agg_claims = 0
        self.agg_sigs = 0
        self.deadline_misses = 0
        self.pipeline_waits = 0
        self.peak_inflight = 0
        # zero-copy ingest plane (ISSUE 20): waves adopted straight from
        # a native staging arena vs. vote-overlapping waves that had to
        # fall back to the Python flatten path
        self.zero_copy_waves = 0
        self.zero_copy_sigs = 0
        self.fallback_waves = 0
        self._next_stats_log = 0.0
        # Telemetry instruments (ISSUE 1), labelled by the service tag.
        # All None when telemetry is off — every hot-path touch below is
        # guarded on ``_tel_wave`` so the disabled cost is one attribute
        # test per wave.
        self._tel_wave = None
        self._tel_claims_submitted = None
        self._tel_claims_unique = None
        self._tel_device_wall = None
        self._tel_host_wall = None
        self._tel_route = None
        self._tel_zero_copy = None
        self._tel_fallback = None
        from .. import telemetry

        if telemetry.enabled():
            reg = telemetry.registry()
            # the backend label keeps multi-backend runs (cpu + tpu + bls
            # services in one process) from aliasing into one series when
            # dashboards aggregate away the per-instance svc tag
            labels = {"svc": self._stats_tag, "backend": kind}
            self._tel_claims_submitted = reg.counter(
                "verify_claims_submitted",
                "Verification claims submitted (pre-dedup, all cores)",
                labels,
            )
            self._tel_claims_unique = reg.counter(
                "verify_claims_unique",
                "Unique claims actually evaluated after cross-core dedup",
                labels,
            )
            self._tel_wave = reg.histogram(
                "verify_wave_sigs",
                "Signatures per coalesced dispatch wave",
                labels,
                bounds=telemetry.SIZE_BOUNDS,
            )
            self._tel_device_wall = reg.float_counter(
                "verify_device_wall_seconds",
                "Wall seconds spent inside device verify dispatches",
                labels,
            )
            self._tel_host_wall = reg.float_counter(
                "verify_host_wall_seconds",
                "Wall seconds spent in host (CPU) claim evaluation",
                labels,
            )
            self._tel_route = {
                r: reg.counter(
                    "verify_route",
                    "Dispatch waves by routing decision",
                    {**labels, "route": r},
                )
                for r in ("device", "mesh", "cpu", "probe", "wait")
            }
            self._tel_zero_copy = reg.counter(
                "ingest_zero_copy_waves",
                "Waves adopted straight from a native ingest arena",
                labels,
            )
            self._tel_fallback = reg.counter(
                "ingest_fallback_waves",
                "Vote-overlapping waves that fell back to Python flatten",
                labels,
            )
            reg.gauge(
                "verify_pending_batches",
                "Submissions queued for the next dispatch wave",
                labels,
                fn=lambda: len(self._pending),
            )
            reg.gauge(
                "verify_inflight_waves",
                "Device dispatch waves currently in flight",
                labels,
                fn=lambda: len(self._inflight),
            )

    @property
    def wave_buckets(self) -> tuple[int, ...]:
        """The fixed wave shapes for this service's backend, resolved
        per access (ISSUE 7): a device host only advertises its
        ``wave_bucket_shapes`` once the device backend materializes at
        warmup, and the mesh backend's shapes depend on the mesh size —
        resolving lazily means the service picks up the mesh-multiple
        ladder the moment it exists instead of freezing the canonical
        default at construction."""
        return resolve_wave_buckets(self.backend)

    @property
    def _device_busy(self) -> bool:
        """Compat view of the pre-pipeline single-in-flight gate: true
        while ANY device dispatch is in flight."""
        return bool(self._inflight)

    # ---- acquisition -------------------------------------------------------

    @classmethod
    def for_backend(cls, backend) -> "AsyncVerifyService":
        """The service for ``backend`` on the running loop.  Device-host
        backends (``async_kind`` set) share one service per (loop, kind)
        pair — in-process committees all submit into the same dispatch
        stream; everything else gets a private inline service.

        ``HOTSTUFF_NO_CLAIM_DEDUP=1`` keeps that one shared stream and
        turns the cross-node dedup off: a wave carries every submitted
        claim on lanes of its own, in submission order, and each core's
        verdicts are read from its own lanes (a co-located committee
        whose every node has its own certificates verified)."""
        kind = getattr(backend, "async_kind", None)
        if kind is None:
            return cls(backend, device=False)
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            # constructed outside a loop (direct-construction tests):
            # a private service — coalescing across cores is lost but
            # nothing binds to a wrong loop
            return cls(backend, device=True)
        # prune entries bound to closed loops (repeated benchmark runs /
        # test loops in one process): each would otherwise pin its loop
        # object plus an idle dispatch loop's slot threads forever
        stale = [
            (k, svc)
            for k, (stored, svc) in cls._registry.items()
            if stored.is_closed()
        ]
        for k, svc in stale:
            cls._registry.pop(k, None)
            svc._shutdown_dispatch()
        key = (id(loop), kind)
        hit = cls._registry.get(key)
        # the stored loop is compared by identity and liveness: an id()
        # reused by a new loop (or a closed loop's leftover) must get a
        # fresh service, or submissions would wait on a dead dispatcher
        if hit is not None and hit[0] is loop and not loop.is_closed():
            return hit[1]
        service = cls(backend, device=True)
        cls._registry[key] = (loop, service)
        return service

    def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
        for lander in list(self._landers):
            lander.cancel()
        self._landers.clear()
        self._shutdown_dispatch()
        for key, (_, service) in list(self._registry.items()):
            if service is self:
                del self._registry[key]

    def _shutdown_dispatch(self) -> None:
        """Stop this service's dispatch loop (service close / stale-loop
        eviction in for_backend / interpreter exit via the loop's own
        atexit hook)."""
        if self._dispatch is not None:
            self._dispatch.close()
            self._dispatch = None

    # ---- submission --------------------------------------------------------

    async def verify_claims(self, claims: list) -> list[bool]:
        """Verdict per claim.  Inline services evaluate immediately;
        device services enqueue and await the coalesced dispatch."""
        if not claims:
            return []
        if not self.device:
            if self._tel_wave is None:
                return eval_claims_sync(self.backend, claims)
            # inline services have no dedup stage: submitted == unique
            t0 = time.perf_counter()
            out = eval_claims_sync(self.backend, claims)
            self._tel_host_wall.add(time.perf_counter() - t0)
            self._tel_claims_submitted.inc(len(claims))
            self._tel_claims_unique.inc(len(claims))
            self._tel_wave.observe(
                sum(claim_sig_count(c) for c in claims)
            )
            return out

        if not self._pending:
            # a wave is named when its batch gets its first claim, so
            # every span from this submit to the verdicts' delivery
            # carries the one serial
            self._wave_serial = next(self._wave_serials)
        with _spans.span("verify.submit", wave=self._wave_serial):
            loop = asyncio.get_running_loop()
            fut: asyncio.Future = loop.create_future()
            self._pending.append((claims, fut))
            if _spans.recorder() is not None:
                self._arrivals.append(time.perf_counter_ns())
            if self._task is None or self._task.done():
                # the dispatcher task drains all pending batches then
                # exits — no long-lived task to leak across loops or
                # shutdowns
                self._task = loop.create_task(
                    self._run(), name="verify-dispatcher"
                )
        return await fut

    # ---- the dispatcher ----------------------------------------------------

    def _deadline_s(self, chunks: int = 1) -> float:
        """Per-dispatch deadline: a stall mid-dispatch must not
        stall the committee.  Backends may raise the floor (BLS: an
        adversarial storm legitimately takes ~0.4 s off-loop;
        re-running it inline would BE the stall).  The floor is one
        backend call's: a wave cut into ``chunks`` calls, run one after
        another on its slot, gets as many floors (the EWMA mixes large
        waves with small ones and cannot stand for either)."""
        return max(
            chunks * getattr(self.backend, "dispatch_deadline_s", 0.1),
            4 * (self._device_ewma_s or 0.1),
        )

    def _cpu_estimate_s(self, n_sigs: int) -> float:
        # the CPU alternative is the batched equation for large waves
        # (eval_claims_sync flat fast path) — but only when that path
        # actually exists on this host; else the per-sig loop
        from .native_ed25519 import available as _native_available

        if n_sigs >= NATIVE_BATCH_MIN and _native_available():
            return cpu_batch_estimate_s(n_sigs)
        return n_sigs * CPU_US_PER_SIG * 1e-6

    # ---- fixed-shape wave packing (ISSUE 6) --------------------------------

    @property
    def _packing_on(self) -> bool:
        """Padding applies only when buckets are configured AND the
        backend opted in (``supports_wave_padding`` — the real ed25519
        device verifiers).  Aggregate-preferring backends (BLS) and CPU
        fallbacks see exactly the submitted claims."""
        return bool(
            self.wave_buckets
            and getattr(self.backend, "supports_wave_padding", False)
        )

    def _pad_claim_tuple(self) -> tuple:
        """The deterministic filler claim for fixed-shape padding: one
        VALID self-contained ed25519 signature over a reserved digest.
        Claim verdicts are per-claim (``all()`` over each claim's own
        span of the flat arrays), so a valid pad can never flip a real
        claim's verdict — and because it is valid, a packed wave that
        falls back to the CPU batch equation still passes when every
        real signature does.  Shared with the native ingest arenas
        (``make_pad_claim``) so adopted pad rows are byte-identical."""
        if self._pad_claim is None:
            self._pad_claim = make_pad_claim()
        return self._pad_claim

    def _pack_wave(self, claims: list, n_sigs: int) -> list[tuple]:
        """A device-routed wave as the backend calls it takes: chunks
        ``(claims, real, lanes)``, each padded to the smallest bucket >=
        its signatures with copies of the pad claim (``real`` claims,
        then pads; ``lanes`` rows in all).  A wave past the largest
        bucket is cut between claims into chunks of at most that
        bucket, so every call hits a warm shape; an exact fit goes
        through unpadded, and so does a single claim past the largest
        bucket (the backend cuts that on its own grid)."""
        buckets = self.wave_buckets
        parts = [(claims, n_sigs)]
        if n_sigs > buckets[-1]:
            parts, start, held = [], 0, 0
            for i, claim in enumerate(claims):
                k = claim_sig_count(claim)
                if held and held + k > buckets[-1]:
                    parts.append((claims[start:i], held))
                    start, held = i, 0
                held += k
            parts.append((claims[start:], held))
        pad = self._pad_claim_tuple()
        chunks = []
        for part, sigs in parts:
            bucket = next((b for b in buckets if b >= sigs), sigs)
            real = len(part)
            if bucket > sigs:
                self.packed_waves += 1
                self.pad_sigs += bucket - sigs
                part = list(part) + [pad] * (bucket - sigs)
            chunks.append((part, real, bucket))
        return chunks

    def warm_buckets(self) -> None:
        """Pre-compile every wave bucket shape (ISSUE 6 warmup): drive
        one pad-only wave per bucket size through the forced-device
        dispatch view, synchronously, so the first real wave of any
        bucket hits a warm jitted callable instead of paying a
        mid-consensus compile.  No-op for inline services, non-padding
        backends, and hosts whose device isn't materialized yet.

        With a mesh-sharded backend the resolved buckets ARE that
        mesh's pad-grid entries (mesh-multiple shapes up to the 4096
        train bucket), so this loop pre-compiles every (bucket x mesh)
        kernel shape the dispatch path can send (ISSUE 7)."""
        if not (self.device and self._packing_on):
            return
        if not getattr(self.backend, "device_ready", True):
            return
        target = getattr(self.backend, "async_backend", self.backend)
        pad = self._pad_claim_tuple()
        for bucket in self.wave_buckets:
            eval_claims_sync(target, [pad] * bucket)

    def _route_device(self, n_sigs: int) -> str:
        """Route this batch: "device", "cpu", "probe", or "wait".

        Never the device before its backend is materialized AND warm (a
        cold jax import or Mosaic compile mid-consensus would blow the
        round timeout — the host sets ``device_ready`` at warmup), and
        never while any in-flight dispatch is OVERDUE: queueing waves
        behind a stalled dispatch was measured to stall the
        whole committee (32-node run collapsed to 1/3 the CPU rate on
        one stall), so a stall pushes traffic to the CPU exactly like
        the old single-in-flight busy gate did.  Below the depth cap,
        compare the occupancy-discounted device EWMA (waves already in
        flight share the dispatch latency) against the CPU estimate.
        "wait": the pipeline is full but healthy and the device is
        still the right answer — the dispatcher queues for a slot
        (bounded by the earliest in-flight deadline) instead of
        spilling to the CPU; or a wave has just been found overdue and
        gets 5 ms for its delivery to reach the loop, once.  "probe":
        the EWMA says the device loses,
        but it's time to re-measure — the caller dispatches a
        measurement-only copy and serves the batch from the CPU, so
        probing a degraded dispatch path never adds wave latency; probes take
        a pipeline slot, so a full pipeline never probes."""
        import os

        if not getattr(self.backend, "device_ready", True):
            return "cpu"
        now = time.monotonic()
        overdue = [s for s, stamp in self._inflight.items() if stamp < now]
        if overdue:
            # an in-flight dispatch blew its deadline — the dispatch
            # path is stalling; route around it until the stuck wave
            # lands.  But the stamp is read on a clock that runs while
            # the process does not: after a pause of the host, or a
            # long pass of a busy loop, the wave has landed and its
            # delivery is queued behind this very task (wan50.low: one
            # wave in a third of the runs served by the CPU for it).
            # So an overdue wave is first given one yield of the loop,
            # once (_wait_for_slot's 5 ms), to be delivered in.
            if self._graced.issuperset(overdue):
                return "cpu"
            self._graced.update(overdue)
            return "wait"
        occupancy = len(self._inflight)
        forced = bool(os.environ.get("HOTSTUFF_FORCE_DEVICE_ROUTE"))
        offload = getattr(self.backend, "always_offload", False)
        if occupancy >= self.pipeline_depth:
            # depth cap: queue when the device is (or is forced to be)
            # the right route, otherwise serve from the CPU.  No probe
            # here — a probe would need the slot we don't have.
            if forced or offload or self._device_ewma_s is None:
                return "wait"
            marginal = self._device_ewma_s * PIPELINE_MARGINAL_COST
            if marginal <= self._cpu_estimate_s(n_sigs):
                return "wait"
            return "cpu"
        if forced:
            # profiling knob (benchmark profile --route device): pin
            # warmed-up waves to the device so the waterfall measures the
            # dispatch pipeline, not the cost-model's mood — gated AFTER
            # the readiness/overdue/depth checks, which stay load-bearing
            return "device"
        if offload:
            # backends whose offload frees the loop unconditionally
            # (BLS native pairings: ctypes releases the GIL) — no
            # cost-model routing needed
            return "device"
        if self._device_ewma_s is None:
            return "device"  # optimistic first dispatch
        marginal = self._device_ewma_s * (
            1.0 if occupancy == 0 else PIPELINE_MARGINAL_COST
        )
        if marginal <= self._cpu_estimate_s(n_sigs):
            return "device"
        if now - self._last_probe >= _PROBE_INTERVAL_S:
            self._last_probe = now
            return "probe"
        return "cpu"

    def _spawn_device(
        self,
        loop,
        chunks: list[tuple],
        measure_only: bool = False,
        deadline: float | None = None,
        wave: "AdoptedWave | None" = None,
        serial: int = 0,
    ):
        """Start a device dispatch of ``chunks`` (``_pack_wave``'s) on
        the dedicated dispatch loop and register it in the in-flight
        table (occupancy + deadline stamp drive routing) under
        ``serial``, the wave's serial (the id of its spans).  The slot
        thread delivers completion back to the
        event loop with ``call_soon_threadsafe``; delivery frees the
        slot, wakes any dispatcher queued in _wait_for_slot, and marks
        exceptions retrieved so abandoned waves (deadline-miss /
        measurement-only) never warn.  Returns ``(completion_future,
        end_holder)``; the slot thread appends its completion stamp to
        ``end_holder`` under the profiler so the lander can charge the
        slot-thread -> loop wakeup gap to verdict.fanout."""
        if self._dispatch is None:
            # one slot thread per pipeline stage: jax.block_until_ready
            # releases the GIL, so while wave N parks on the device,
            # wave N+1 stages on the next slot — that overlap IS the
            # pipeline.  The backends are thread-compatible (table
            # rebuilds publish atomically under their own lock) and pool
            # staging scratch per thread, so each slot reuses its own
            # preallocated buffers wave after wave.
            self._dispatch = _DispatchLoop(self.pipeline_depth)
        # guarded-by: gil -- written here on the event loop, popped by
        # _deliver (loop) and by _on_done's loop-closed fallback (slot
        # thread); every access is a single dict bytecode, atomic under
        # the GIL, and the routing reads tolerate one-wave staleness
        self._inflight[serial] = time.monotonic() + (
            deadline
            if deadline is not None
            else self._deadline_s(len(chunks))
        )
        self.peak_inflight = max(self.peak_inflight, len(self._inflight))
        rec = _spans.recorder()
        t_spawn = time.perf_counter_ns() if rec is not None else None
        if rec is not None:
            # occupancy annotation (value encoded in the dur field, not
            # a duration — rendered as a counter on the Perfetto track)
            rec.add("pipeline.occupancy", t_spawn, len(self._inflight))
        end_holder: list[int] = []
        fut: asyncio.Future = loop.create_future()

        def _deliver(result, exc):
            # on the event loop: free the slot, resolve the wave future
            self._inflight.pop(serial, None)
            self._graced.discard(serial)
            if self._slot_free is not None:
                self._slot_free.set()
            if fut.cancelled():
                return
            if exc is None:
                fut.set_result(result)
            elif measure_only:
                log.warning("device measurement dispatch failed: %s", exc)
                fut.set_result(None)
            else:
                fut.set_exception(exc)
                # mark retrieved: the lander re-raises via result(), but
                # a deadline-missed wave is abandoned — without this the
                # GC would warn about the never-retrieved exception
                fut.exception()

        def _on_done(result, exc):
            # on the slot thread: hop back to the service's event loop
            try:
                loop.call_soon_threadsafe(_deliver, result, exc)
            except RuntimeError:
                # the loop closed mid-flight (benchmark loop teardown /
                # interpreter exit): free the slot directly so routing
                # never sees a phantom in-flight wave
                self._inflight.pop(serial, None)

        self._dispatch.submit(
            lambda: self._dispatch_sync(
                chunks, t_spawn, end_holder, wave, serial
            ),
            _on_done,
        )
        return fut, end_holder

    def _dispatch_sync(
        self,
        chunks: list[tuple],
        t_spawn: int | None = None,
        end_holder: list | None = None,
        wave: "AdoptedWave | None" = None,
        serial: int = 0,
    ) -> list[bool]:
        """Slot-thread body: evaluate the wave's chunks one after
        another on the forced-device dispatch view, timing the dispatch
        for the routing EWMA; returns the real claims' verdicts, pads
        dropped.  An adopted zero-copy wave (one chunk, its claims)
        stages from its arena columns instead of flattening claim
        tuples (released inside eval_claims_arena).  The
        ``dispatch.wall`` frame hands ``wave=serial`` down to the stage
        spans inside it (``flatten`` ... ``readback``), each chunk's
        ``dispatch.chunk`` frame its ``chunk`` and ``lanes``."""
        rec = _spans.recorder()
        if rec is not None and t_spawn is not None:
            # dispatch-loop handoff -> slot thread entry (thread
            # wakeup + any queueing behind a previous dispatch)
            rec.add(
                "stage.slot_wait",
                t_spawn,
                time.perf_counter_ns() - t_spawn,
                wave=serial,
            )
        target = getattr(self.backend, "async_backend", self.backend)
        t0 = time.perf_counter()
        with _spans.span("dispatch.wall", wave=serial):
            if wave is not None:
                out = eval_claims_arena(target, wave, chunks[0][0])
            else:
                out = []
                for i, (part, real, lanes) in enumerate(chunks):
                    with _spans.span("dispatch.chunk", chunk=i, lanes=lanes):
                        out += eval_claims_sync(target, part)[:real]
        wall = time.perf_counter() - t0
        if rec is not None and end_holder is not None:
            end_holder.append(time.perf_counter_ns())
        if self._tel_device_wall is not None:
            self._tel_device_wall.add(wall)
        ewma = self._device_ewma_s
        # guarded-by: gil -- written on the slot thread, read by the
        # loop-side router (_route_device/_deadline_s); a float rebind
        # is one atomic store and a stale read only skews the EWMA by
        # one sample
        self._device_ewma_s = (
            wall if ewma is None else (1 - _EWMA_ALPHA) * ewma + _EWMA_ALPHA * wall
        )
        return out

    async def _wait_for_slot(self) -> None:
        """Depth-cap backpressure: park until an in-flight wave lands or
        the earliest in-flight deadline expires (the wave went overdue —
        the next routing pass serves from the CPU); behind a wave that
        is overdue already, for the 5 ms its delivery is given."""
        if self._slot_free is None:
            self._slot_free = asyncio.Event()
        self._slot_free.clear()
        now = time.monotonic()
        earliest = min(self._inflight.values(), default=now)
        if len(self._inflight) < self.pipeline_depth and earliest >= now:
            return  # a wave landed between the route decision and here
        timeout = max(0.005, earliest - now + 0.005)
        try:
            await asyncio.wait_for(self._slot_free.wait(), timeout)
        except asyncio.TimeoutError:
            pass

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        # fresh per dispatcher spawn: the event must belong to the loop
        # this dispatcher runs on (services can outlive benchmark loops)
        self._slot_free = asyncio.Event()
        while True:
            # let every task woken by the same network wave enqueue its
            # claims before the batch departs (two passes: receiver ->
            # core handoff, core -> submit)
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            if self.coalesce_window_s > 0.0 and self._pending:
                # QC+TC coalescing (ISSUE 6): hold the wave open for a
                # round window so both certificate kinds produced by
                # the same round merge into ONE device dispatch — the
                # verdict table fans each claim back to its own
                # submitters on readback
                await asyncio.sleep(self.coalesce_window_s)
            batch, self._pending = self._pending, []
            arrivals, self._arrivals = self._arrivals, []
            if not batch:
                return  # drained — the next submit respawns the task
            # what the cores handed in, in submission order
            submitted = [c for cs, _ in batch for c in cs]
            handed_sigs = sum(claim_sig_count(c) for c in submitted)
            # the serial the batch's first submit took (verify_claims)
            serial = self._wave_serial
            rec = _spans.recorder()
            wave_t0 = min(arrivals) if (rec is not None and arrivals) else None
            if wave_t0 is not None:
                rec.add(
                    "coalesce.wait",
                    wave_t0,
                    time.perf_counter_ns() - wave_t0,
                    wave=serial,
                )
            # Deduplicate identical claims across submissions: a claim's
            # verdict is a PURE function of (digest, pk, sig) bytes, so
            # one evaluation serves every submitter — in a co-located
            # committee one broadcast proposal arrives at every core in
            # the same wave, and without dedup the service would verify
            # the same certificate once per node (n x the work this
            # layer exists to avoid).  Each core still applies its OWN
            # stake/quorum/safety rules to the verdicts; no per-node
            # acceptance state crosses node boundaries.  With the dedup
            # off (HOTSTUFF_NO_CLAIM_DEDUP) no verdict does either: the
            # wave's lanes are the submitted signatures, in submission
            # order, and _verdicts_by_submission reads each core's own.
            with _spans.span(
                "verify.collect",
                wave=serial,
                sigs=handed_sigs,
                claims=len(submitted),
            ):
                if self._dedup:
                    claims = list(dict.fromkeys(submitted))
                    n_sigs = sum(claim_sig_count(c) for c in claims)
                else:
                    claims, n_sigs = submitted, handed_sigs
                agg_in_wave = [c for c in claims if c[0] == "agg"]
                if agg_in_wave:
                    self.agg_claims += len(agg_in_wave)
                    self.agg_sigs += sum(len(c[3]) for c in agg_in_wave)
                self.dispatches += 1
                if self._tel_wave is not None:
                    self._tel_claims_submitted.inc(len(submitted))
                    self._tel_claims_unique.inc(len(claims))
                    self._tel_wave.observe(n_sigs)

            # zero-copy adoption (ISSUE 20): if the native transport
            # packed this wave's votes into a staging arena and the
            # claim stream matches the packed prefix exactly, adopt the
            # arena — downstream flatten/prepare become frombuffer
            # views.  Passive accessor: the verify path never triggers
            # a native build; only receivers create the plane.
            adopted = None
            ing = zero_copy_ingest_if_active()
            if ing is not None and ing.active:
                with _spans.span("native.pack", wave=serial):
                    fb_before = ing.fallback_waves
                    adopted = ing.try_adopt(claims, self.wave_buckets)
                if adopted is not None:
                    self.zero_copy_waves += 1
                    self.zero_copy_sigs += n_sigs
                    if self._tel_zero_copy is not None:
                        self._tel_zero_copy.inc()
                elif ing.fallback_waves != fb_before:
                    self.fallback_waves += 1
                    if self._tel_fallback is not None:
                        self._tel_fallback.inc()
            try:
                with _spans.span("route.decide", wave=serial, sigs=n_sigs):
                    route = self._route_device(n_sigs)
                waited = False
                while route == "wait":
                    # full pipeline, healthy and device-preferred: queue
                    # for a slot (wave K+1 backpressure) instead of
                    # spilling to the CPU, then re-route — a freed slot
                    # goes to the device, an expired deadline to the CPU
                    if not waited:
                        waited = True
                        self.pipeline_waits += 1
                        if self._tel_route is not None:
                            self._tel_route["wait"].inc()
                    t_w = (
                        time.perf_counter_ns() if rec is not None else None
                    )
                    await self._wait_for_slot()
                    if t_w is not None:
                        rec.add(
                            "pipeline.wait",
                            t_w,
                            time.perf_counter_ns() - t_w,
                            wave=serial,
                        )
                    route = self._route_device(n_sigs)
                # counted where the route is, with device_sigs or
                # cpu_sigs below, so that no stats line holds a wave's
                # submitted signatures without its evaluated ones
                self.submitted_sigs += handed_sigs
                if self._tel_route is not None:
                    # sharded backends label their device waves "mesh"
                    # so dashboards separate multi-chip dispatches
                    self._tel_route[
                        self._device_route_label
                        if route == "device"
                        else route
                    ].inc()
                # what a slot thread is handed: (claims, how many of
                # them are real, rows); an adopted arena holds its own
                # bucket-shaped rows
                chunks = [
                    (claims, len(claims), adopted.rows if adopted else n_sigs)
                ]
                if (
                    route in ("device", "probe")
                    and self._packing_on
                    and adopted is None
                ):
                    # fixed-shape wave (ISSUE 6): pad to the bucket so
                    # the dispatch hits a warm jitted callable, a wave
                    # past the largest bucket cut into chunks that do.
                    # Probes pack too — they measure the shape real
                    # waves use.  Adopted waves skip this: the arena is
                    # already bucket-shaped with native-padded rows.
                    with _spans.span("stage.pack", wave=serial, sigs=n_sigs):
                        chunks = self._pack_wave(claims, n_sigs)
                if route == "probe":
                    # measurement-only device dispatch: results are
                    # discarded (EWMA updates when it lands); the batch
                    # itself is served from the CPU so a degraded dispatch path
                    # never adds wave latency
                    self.probe_dispatches += 1
                    with _spans.span("verify.spawn", wave=serial):
                        self._spawn_device(
                            loop, chunks, measure_only=True,
                            wave=adopted, serial=serial,
                        )
                    adopted = None  # released by the probe dispatch
                if route == "device":
                    self.device_dispatches += 1
                    if self._device_route_label == "mesh":
                        self.mesh_dispatches += 1
                    self.device_sigs += n_sigs
                    lanes = sum(rows for _, _, rows in chunks)
                    self.lanes += lanes
                    self.chunks += len(chunks)
                    deadline = self._deadline_s(len(chunks))
                    with _spans.span(
                        "verify.spawn",
                        wave=serial,
                        sigs=n_sigs,
                        # the rows the device is handed, pads included
                        bucket=lanes,
                        chunks=len(chunks),
                    ):
                        exec_fut, end_holder = self._spawn_device(
                            loop, chunks, deadline=deadline,
                            wave=adopted, serial=serial,
                        )
                    adopted = None  # released by the slot thread
                    # async readback (ISSUE 5): the dispatcher does NOT
                    # await the device — a per-wave lander task lands
                    # this wave's verdicts when its completion future
                    # resolves, so waves complete out of order and a
                    # failure poisons only its own batch.  The
                    # dispatcher loops straight back to staging the
                    # next wave.
                    lander = loop.create_task(
                        self._land_device(
                            batch, claims, exec_fut, end_holder,
                            wave_t0, deadline, serial,
                        ),
                        name="verify-lander",
                    )
                    self._landers.add(lander)
                    lander.add_done_callback(self._landers.discard)
                    continue
                self.cpu_dispatches += 1
                self.cpu_sigs += n_sigs
                if adopted is not None:
                    wave_held, adopted = adopted, None
                    await self._serve_cpu_arena(batch, claims, wave_held)
                else:
                    await self._serve_cpu(batch)
                if wave_t0 is not None:
                    rec.add(
                        "e2e",
                        wave_t0,
                        time.perf_counter_ns() - wave_t0,
                        wave=serial,
                    )
                self._log_stats()
            except asyncio.CancelledError:
                if adopted is not None:
                    adopted.release()
                for _, fut in batch:
                    if not fut.done():
                        fut.cancel()
                raise
            except Exception as e:  # noqa: BLE001 — backend failure must
                # reach every waiter, not kill the dispatcher
                if adopted is not None:
                    adopted.release()
                log.warning("verify dispatch failed: %s", e)
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(
                            RuntimeError(f"verify dispatch failed: {e}")
                        )
                continue

    async def _serve_cpu(self, batch) -> None:
        # CPU serving holds the GIL either way (measured) — run
        # inline, but per SUBMISSION with yields between, so a
        # large coalesced wave doesn't block the loop in one
        # chunk (each core's future resolves as soon as its own
        # claims are done, matching the inline service's latency
        # profile).  The memo carries each unique claim's
        # verdict across the wave's submissions (same purity
        # argument as the batch dedup in _run).
        cpu = getattr(self.backend, "cpu_backend", self.backend)
        memo: dict = {}
        for cs, fut in batch:
            # with the dedup off a submission is evaluated whole, for
            # its submitter alone
            todo = [c for c in cs if c not in memo] if self._dedup else cs
            results = []
            if todo:
                t0 = time.perf_counter()
                results = eval_claims_sync(cpu, todo)
                if self._tel_host_wall is not None:
                    self._tel_host_wall.add(time.perf_counter() - t0)
            if self._dedup:
                memo.update(zip(todo, results))
                results = [memo[c] for c in cs]
            if not fut.done():
                fut.set_result(results)
            await asyncio.sleep(0)

    async def _serve_cpu_arena(self, batch, claims: list, wave) -> None:
        """CPU serving for an adopted zero-copy wave: ONE native batch
        equation straight from the arena columns covers every unique
        claim (no b"".join flatten, no per-claim re-verify), then
        verdicts fan out per submission exactly like _serve_cpu."""
        cpu = getattr(self.backend, "cpu_backend", self.backend)
        t0 = time.perf_counter()
        results = eval_claims_arena(cpu, wave, claims)
        if self._tel_host_wall is not None:
            self._tel_host_wall.add(time.perf_counter() - t0)
        for fut, verdicts in self._verdicts_by_submission(
            batch, claims, results
        ):
            if not fut.done():
                fut.set_result(verdicts)
            await asyncio.sleep(0)

    def _verdicts_by_submission(self, batch, claims: list, results: list):
        """``(future, its verdicts)`` for each submission of a wave
        whose ``claims`` evaluated to ``results``.  With the dedup on, a
        claim's one verdict serves every core that handed it in; off,
        ``claims`` is the submissions laid end to end and each core
        reads its own lanes."""
        if self._dedup:
            verdict = dict(zip(claims, results))
            for cs, fut in batch:
                yield fut, [verdict[c] for c in cs]
        else:
            at = 0
            for cs, fut in batch:
                yield fut, results[at : at + len(cs)]
                at += len(cs)

    async def _land_device(
        self,
        batch,
        claims: list,
        exec_fut,
        end_holder: list,
        wave_t0: int | None,
        deadline: float,
        serial: int = 0,
    ) -> None:
        """Land one in-flight device wave: await its completion future
        (bounded by the dispatch deadline), fan its verdicts out to this
        wave's waiters ONLY.  Deadline overrun serves this batch from
        the CPU and lets the stuck dispatch land as a (bad) EWMA
        measurement; a backend exception poisons this wave's futures and
        nothing else (per-wave error isolation)."""
        rec = _spans.recorder()
        try:
            done, _ = await asyncio.wait({exec_fut}, timeout=deadline)
            if exec_fut not in done:
                self.deadline_misses += 1
                self._last_probe = time.monotonic()
                log.warning(
                    "device verify dispatch overran its %.0f ms "
                    "deadline; serving the batch from the CPU",
                    deadline * 1e3,
                )
                await self._serve_cpu(batch)
                if rec is not None and wave_t0 is not None:
                    rec.add(
                        "e2e",
                        wave_t0,
                        time.perf_counter_ns() - wave_t0,
                        wave=serial,
                    )
                self._log_stats()
                return
            results = exec_fut.result()
        except asyncio.CancelledError:
            for _, fut in batch:
                if not fut.done():
                    fut.cancel()
            raise
        except Exception as e:  # noqa: BLE001 — a failed wave must reach
            # its own waiters, and ONLY its own waiters
            log.warning("verify dispatch failed: %s", e)
            for _, fut in batch:
                if not fut.done():
                    fut.set_exception(
                        RuntimeError(f"verify dispatch failed: {e}")
                    )
            return
        fan_t0 = end_holder[0] if (rec is not None and end_holder) else None
        with _spans.span("verify.deliver", wave=serial):
            for fut, verdicts in self._verdicts_by_submission(
                batch, claims, results
            ):
                if not fut.done():
                    fut.set_result(verdicts)
        if rec is not None:
            end_ns = time.perf_counter_ns()
            if fan_t0 is not None:
                # worker completion -> every waiter's future resolved
                # (captures the executor -> loop wakeup gap)
                rec.add(
                    "verdict.fanout", fan_t0, end_ns - fan_t0, wave=serial
                )
            if wave_t0 is not None:
                rec.add("e2e", wave_t0, end_ns - wave_t0, wave=serial)
        self._log_stats()

    def _log_stats(self) -> None:
        now = time.monotonic()
        if self.device and now >= self._next_stats_log:
            # NOTE: this log entry is used to compute performance
            # (benchmark log-scrape contract): device-vs-CPU routing
            # split and the measured dispatch EWMA.
            self._next_stats_log = now + 5.0
            log.info(
                "Verify service stats [%s]: dispatches=%d device=%d "
                "cpu=%d probe=%d device_sigs=%d cpu_sigs=%d "
                "deadline_misses=%d waits=%d depth=%d mesh=%d "
                "agg=%d agg_sigs=%d ewma_ms=%.1f zc=%d fb=%d "
                "submitted_sigs=%d lanes=%d chunks=%d h2d=%d calls=%d",
                self._stats_tag,
                self.dispatches,
                self.device_dispatches,
                self.cpu_dispatches,
                self.probe_dispatches,
                self.device_sigs,
                self.cpu_sigs,
                self.deadline_misses,
                self.pipeline_waits,
                self.pipeline_depth,
                self.mesh_dispatches,
                self.agg_claims,
                self.agg_sigs,
                (self._device_ewma_s or 0.0) * 1e3,
                self.zero_copy_waves,
                self.fallback_waves,
                self.submitted_sigs,
                self.lanes,
                self.chunks,
                # the backend's own: host arrays handed to jax and jitted
                # calls, one of each a chunk (tpu/ed25519.py)
                *getattr(self.backend, "device_counters", lambda: (0, 0))(),
            )


__all__ = [
    "AdoptedWave",
    "AsyncVerifyService",
    "ZeroCopyIngest",
    "claim_sig_count",
    "eval_claims_arena",
    "eval_claims_sync",
    "flatten_claims",
    "ingest_note_frame",
    "make_pad_claim",
    "pipeline_depth_from_env",
    "wave_buckets_from_env",
    "resolve_wave_buckets",
    "coalesce_window_s_from_env",
    "zero_copy_ingest",
    "zero_copy_ingest_if_active",
    "CPU_US_PER_SIG",
    "DEFAULT_INGEST_RING_DEPTH",
    "DEFAULT_PIPELINE_DEPTH",
    "DEFAULT_WAVE_BUCKETS",
    "PIPELINE_MARGINAL_COST",
]
