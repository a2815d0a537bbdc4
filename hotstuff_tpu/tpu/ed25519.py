"""Batched Ed25519 verification on TPU — the crypto hot kernel.

This is the TPU-native replacement for the reference's QC-verify hot spot
(``Signature::verify_batch``, reference crypto/src/lib.rs:213-226, called
from QC::verify at consensus/src/messages.rs:195) and the per-signature
verifies on the proposal path (messages.rs:64,142,256,305-311).

Verification equation: a signature (R, s) by pubkey A over message M is
valid iff [s]B == R + [k]A with k = SHA-512(R||A||M) mod L, i.e. iff
P := [s]B + [k](-A) compresses to the R bytes. The kernel evaluates P for
the whole batch with one fused double-scalar multiplication and compares
compressed encodings, so:

- SHA-512 and the mod-L reductions stay on the host (cheap, ~us each);
- committee public keys are decompressed ONCE on the host and cached —
  the committee is fixed per epoch, so steady-state verification does no
  square roots at all, on either side;
- R is never decompressed: the compressed-encoding comparison subsumes
  point validity (an R that decodes to no curve point can never equal a
  compressed P).

Semantics vs the CPU path: cofactorless ("strict") verification with
rejection of s >= L and non-canonical R encodings — agreeing with the
oracle `ed25519_ref.verify` on every input (tested in
tests/test_tpu_ed25519.py). Batches are padded to a small set of static
shapes to bound XLA recompilation.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp

from ..crypto import ed25519_ref as ref
from ..telemetry import spans as _spans
from . import curve, exe_store as _exe_store, field as F

# Padded batch shapes (powers of 4) to bound compilation count.
PAD_SIZES = (1, 4, 16, 64, 256, 1024, 4096)

# Pallas pad shapes: lane-aligned, capped at 1024 per dispatch (larger
# batches chunk).  Each shape's program is built once and then loaded
# from the executable store by every later process (tpu/exe_store.py).
PALLAS_PAD_SIZES = (128, 256, 1024)


def _verify_impl(ax, ay, az, at, s_bits, k_bits, r_y, r_sign):
    """Device kernel body: bool[batch] validity.

    ax..at: [batch, 20] limbs of the NEGATED public-key points.
    s_win, k_win: [NWIN, batch] MSB-first 4-bit scalar windows.
    r_y: [batch, 20] raw limb split of R's low 255 bits.
    r_sign: [batch] bit 255 of R.
    """
    p = curve.dual_scalar_mult(s_bits, k_bits, (ax, ay, az, at))
    return curve.compressed_equals(p, r_y, r_sign)


#: columns of a wave's staging buffer, one ``uint8`` row a signature:
#: the signature (R, then s), the challenge scalar k, and the key's row
#: in the committee tables as four little-endian bytes
SIG_COLS, K_COLS, ROW_COLS = slice(0, 64), slice(64, 96), slice(96, 100)
WAVE_COLS = 100

# MSB-first shifts of the windows of one byte, and for each 13-bit limb
# of R's low 255 bits the byte its lowest bit lies in and where
_WIN_SHIFTS = np.arange(8 - curve.WINDOW, -1, -curve.WINDOW)
_LIMB_BYTE, _LIMB_SHIFT = np.divmod(F.LIMB_BITS * np.arange(F.NLIMBS), 8)


def _windows_msb(scalars):
    """uint8 [n, 32] little-endian scalars -> int32 [NWIN, n] MSB-first
    4-bit windows: ``_bytes_to_windows_msb`` transposed, by shifts."""
    b = scalars[:, ::-1].astype(jnp.int32)
    win = (b[:, :, None] >> _WIN_SHIFTS) & ((1 << curve.WINDOW) - 1)
    return win.reshape(b.shape[0], curve.NWIN).T


def unpack_wave(tables, buf):
    """The kernel's eight operands from a staged wave, on the device:
    ``buf`` is uint8 [n, WAVE_COLS], ``tables`` the four device-resident
    coordinate tables of the negated committee points.  Bit for bit what
    ``_bytes_to_windows_msb`` / ``_bytes_rows_to_limbs`` and a host
    gather give (tests/test_tpu_ed25519.py holds them to each other)."""
    r = buf[:, :32].astype(jnp.int32)
    r_sign = r[:, 31] >> 7
    # a limb's 13 bits lie in at most three bytes; bit 255 is the sign
    r = jnp.pad(r.at[:, 31].set(r[:, 31] & 0x7F), ((0, 0), (0, 1)))
    word = (
        r[:, _LIMB_BYTE]
        | r[:, _LIMB_BYTE + 1] << 8
        | r[:, _LIMB_BYTE + 2] << 16
    )
    r_y = (word >> _LIMB_SHIFT) & F.MASK
    row = buf[:, ROW_COLS].astype(jnp.int32)
    idx = row[:, 0] | row[:, 1] << 8 | row[:, 2] << 16 | row[:, 3] << 24
    ax, ay, az, at = (t[idx] for t in tables)
    s_win, k_win = _windows_msb(buf[:, 32:64]), _windows_msb(buf[:, K_COLS])
    return ax, ay, az, at, s_win, k_win, r_y, r_sign


def wave_fn(pallas: bool, interpret: bool = False):
    """``(tables, buffer) -> bool[rows]``: the decomposition and the key
    gather of ``unpack_wave``, then the kernel.  ``pallas`` selects the
    fused VMEM-resident Pallas dispatch (tpu/pallas_dsm.py: TPU only,
    rows a multiple of its LANE_TILE, which the pad sizes guarantee;
    the XLA epilogue was ~2 ms of sequential HBM round-trips), else the
    portable XLA kernel.  One function for the single-device entry below
    and, per shard, for the mesh verifier (parallel/mesh.py)."""

    def verify_wave(tables, buf):
        ax, ay, az, at, s_win, k_win, r_y, r_sign = unpack_wave(tables, buf)
        if not pallas:
            return _verify_impl(ax, ay, az, at, s_win, k_win, r_y, r_sign)
        from . import pallas_dsm

        return pallas_dsm.verify_compressed(
            s_win, k_win, (ax, ay, az, at), r_y, r_sign, interpret=interpret
        )

    return verify_wave


@lru_cache(maxsize=None)
def _wave_entry(pallas: bool, donate: bool):
    """The one jitted entry a wave is dispatched through, compiled once
    a pad shape.  ``donate`` (ISSUE 6) hands the wave's buffer, a
    per-wave temporary, back to XLA; the tables are the epoch-static
    gather source and never donated."""
    return jax.jit(wave_fn(pallas), donate_argnums=(1,) if donate else ())


_LIMB_WEIGHTS = (1 << np.arange(F.LIMB_BITS, dtype=np.int32)).astype(np.int32)

# big-endian bytes of the group order, for the vectorized s < L check
_L_BE = np.frombuffer(ref.L.to_bytes(32, "big"), np.uint8)


_WIN_WEIGHTS = (1 << np.arange(curve.WINDOW - 1, -1, -1)).astype(np.int32)


def _bytes_to_windows_msb(rows: np.ndarray) -> np.ndarray:
    """[n, W] little-endian scalar bytes -> [n, 2W] MSB-first 4-bit
    windows (W = 32 for full scalars < L < 2^253).  With
    ``_bytes_rows_to_limbs`` the plain numpy reference of
    ``unpack_wave``: off the production path since a wave is decomposed
    on the device."""
    bits = np.unpackbits(rows[:, ::-1], axis=1, bitorder="big").astype(np.int32)
    nwin = rows.shape[1] * 8 // curve.WINDOW
    groups = bits.reshape(rows.shape[0], nwin, curve.WINDOW)
    return groups @ _WIN_WEIGHTS


def _bytes_rows_to_limbs(rows: np.ndarray) -> np.ndarray:
    """[n, 32] little-endian encodings -> [n, NLIMBS] raw 13-bit split of
    the low 255 bits (NOT reduced mod p — see compressed_equals)."""
    bits = np.unpackbits(rows, axis=1, bitorder="little")[:, :255]
    bits = np.pad(bits, [(0, 0), (0, F.NLIMBS * F.LIMB_BITS - 255)])
    groups = bits.reshape(rows.shape[0], F.NLIMBS, F.LIMB_BITS).astype(np.int32)
    return groups @ _LIMB_WEIGHTS


class BatchVerifier:
    """Host-side driver: prepares batches, caches committee points, runs the
    jitted kernel. Thread-compatible with the asyncio node (pure function +
    caches keyed by immutable bytes).

    Hybrid routing: batches smaller than ``min_device_batch`` are
    verified on the CPU backend instead — kernel dispatch has a fixed
    cost (the dispatch latency) that swamps the work of a handful of
    signatures, so the device only sees batches where it pays off.  Set
    ``min_device_batch=0`` to force everything onto the device (tests
    do, so the kernel path is what's exercised)."""

    def __init__(
        self,
        min_device_batch: int = 64,
        use_pallas: bool | None = None,
        exe_dir: str | None = None,
    ):
        # pk bytes -> (ax, ay, az, at) limb rows of the negated point, or None
        self._point_cache: dict[bytes, tuple | None] = {}
        # Vectorized prepare path: pk bytes -> row index into the stacked
        # point table (row 0 is a zero dummy for invalid items), rebuilt
        # lazily when new keys enter the cache.  Committee keys are fixed
        # per epoch, so steady state is one fancy-index gather per batch
        # instead of a per-item Python copy loop (measured 11-28 ms of
        # GIL-held prep per 736-sig wave before this).
        self._row_index: dict[bytes, int] = {}
        # the published build: (coordinate tables, row index) or None.
        # _table_lock serializes cache inserts/invalidation and rebuilds:
        # this object is shared between the event loop and the async
        # verify service's worker thread, and an unlocked rebuild racing
        # an insert can either crash (dict changed size during
        # iteration) or publish a build missing the new key while
        # clobbering the staleness marker — after which that key's valid
        # signatures map to the zero dummy row forever.
        import threading

        self._table_lock = threading.Lock()
        self._tables: tuple | None = None
        # Device-resident committee key cache (ISSUE 5): the stacked
        # coordinate tables staged on device ONCE per rebuild (committee
        # keys are static per epoch), so each wave ships only its rows'
        # indices, in its one buffer, and gathers coordinates inside the
        # jitted call.  _device_src identifies the host build the staged
        # copy mirrors.
        self._device_tables: tuple | None = None
        self._device_src: tuple | None = None
        # Per-thread staging scratch, keyed by padded size: the pipeline
        # runs prepare() on up to pipeline_depth worker threads at once,
        # so buffers are thread-local rather than shared (reuse across
        # waves without a lock).  The dispatch loop's slot threads are
        # long-lived (ISSUE 6), so these pools ARE the preallocated
        # staging-buffer ring: one persistent set per slot.
        self._scratch = threading.local()
        # Host arrays handed to jax and jitted calls made, cumulative:
        # a wave is one of each (the verify service prints both beside
        # ``chunks=``), a table rebuild four arrays more.  The slot
        # threads share them, hence the lock.
        self.h2d = 0
        self.calls = 0
        self._count_lock = threading.Lock()
        # Challenge-hash memo: k = H(R||A||M) is a pure function of the
        # claim bytes, and fixed-shape padding re-stages the SAME pad
        # claim every wave — memoizing makes pad lanes (and re-verified
        # claims) cost a dict hit instead of a SHA-512 each.  Bounded;
        # cleared wholesale when full (GIL-atomic ops only, so the
        # pipeline's slot threads share it without a lock).
        self._challenge_memo: dict[tuple, bytes] = {}
        # buffer donation decision (resolved lazily, see donate_buffers)
        self._donate: bool | None = None
        # The Pallas VMEM-resident kernel is the fast path on real TPU
        # hardware; the XLA kernel is the portable fallback (CPU tests,
        # sharded-mesh subclass).  use_pallas=None defers autodetection
        # to the first device dispatch — probing the backend in
        # __init__ would initialize JAX in every process that merely
        # CONSTRUCTS a verifier (e.g. small-committee nodes whose
        # batches all route to the CPU hybrid path and that may not be
        # able to claim the device at all).
        self._use_pallas = use_pallas
        if use_pallas is not None:
            self.pad_sizes = PALLAS_PAD_SIZES if use_pallas else PAD_SIZES
        else:
            self.pad_sizes = None  # resolved with use_pallas
        self.min_device_batch = min_device_batch
        self._cpu = None  # lazy CpuVerifier for small batches
        # pad shape -> FirstCallTimer.take() of its warmup call
        self.warm_report: dict[int, dict] = {}
        # (donate, buffer shape, table shape) -> the program a wave of
        # those shapes runs; exe_dir names the executable store's
        # directory (None: beside the compile cache, Pallas only), and
        # _exe_report is what the store said of the last program it gave
        self._programs: dict[tuple, object] = {}
        self._program_lock = threading.Lock()
        self._exe_dir = exe_dir
        self._exe_store = None
        self._exe_report: dict | None = None

    @property
    def use_pallas(self) -> bool:
        if self._use_pallas is None:
            self._use_pallas = jax.default_backend() == "tpu"
        return self._use_pallas

    @property
    def kernel_name(self) -> str:
        return "pallas" if self.use_pallas else "xla"

    def describe(self) -> dict:
        """Where and how this verifier runs — the node's boot line."""
        from . import device_info

        return {
            **device_info(),
            "kernel": self.kernel_name,
            "pad_shapes": list(self._padded_sizes()),
            "warm": {str(k): v for k, v in self.warm_report.items()},
        }

    def _padded_sizes(self) -> tuple[int, ...]:
        if self.pad_sizes is None:
            self.pad_sizes = PALLAS_PAD_SIZES if self.use_pallas else PAD_SIZES
        return self.pad_sizes

    def precompute(self, pubkeys: list[bytes]) -> None:
        """Decompress + negate committee keys ahead of time (epoch
        setup) so no point decompression lands inside a QC verify."""
        for pk in pubkeys:
            self._neg_point(pk)

    def warmup(self, batch: int | None = None) -> None:
        """Compile (or load) the device kernel BEFORE entering the
        consensus hot path.  The first call at each pad shape costs
        seconds to tens of seconds unless the executable store holds
        its program — paid here, once, at node boot, instead of on the
        first QC verify where it would blow through the round timeout.
        ``warm_report`` keeps where each shape's seconds went, and
        whether its program was loaded or built (``exe``).

        ``batch`` is the largest batch the caller expects (the committee
        size: QC/TC verification batches never exceed it) — warming the
        shape THAT batch pads to is the point; the min_device_batch
        floor alone would warm a smaller shape and leave the real QC
        shape cold."""
        from ..crypto import ed25519_ref as ref

        seed = b"\x5a" * 32
        msg = b"hotstuff_tpu verifier warmup"
        pk = ref.public_from_seed(seed)
        sig = ref.sign(seed, msg)
        n = max(batch or 0, self.min_device_batch, 1)  # force device path
        # Warm EVERY pad shape a production batch can land on: QCs are
        # 2f+1 <= committee size, so any pad size at or below the
        # committee's own pad is reachable (e.g. committee 150 pads to
        # 256, but its 101-vote QCs pad to 128 — leaving 128 cold would
        # put a Mosaic compile inside the consensus hot path, exactly
        # what this warmup exists to prevent).
        grid = self._padded_sizes()
        ceiling = next((p for p in grid if n <= p), grid[-1])
        floor = max(self.min_device_batch, 1)  # smaller pads never reach
        # the device (the hybrid routing sends those batches to the CPU)
        # ... EXCEPT through the async service's fixed-shape padding
        # (ISSUE 6): a small wave the cost model routes to the device
        # pads UP to the smallest bucket, so that shape must be warm too
        if getattr(self, "supports_wave_padding", False):
            from ..crypto.async_service import (
                make_pad_claim,
                resolve_wave_buckets,
            )

            # a padded wave brings the pad claim's key: into the point
            # cache before any shape compiles, or the first production
            # wave could grow the staged table (a shape of the jitted
            # entry's argument) and compile again at every pad shape,
            # inside the first waves' deadlines (my chip run, PR 32: two
            # waves of every boot served by the CPU)
            self._neg_point(make_pad_claim()[2])
            # same resolution the service uses: explicit env ladder
            # wins, else this backend's own advertised shapes (the mesh
            # verifier's mesh-multiple buckets, ISSUE 7)
            buckets = resolve_wave_buckets(self)
            if buckets:
                floor = min(floor, buckets[0])
        sizes = [p for p in grid if floor <= p <= ceiling] or [n]
        from . import FirstCallTimer

        # one lane's R with a bit flipped: the host takes it, only the
        # kernel can refuse it, so a program that answers "all valid"
        # (or "all invalid") stops the boot here
        forged = bytes([sig[0] ^ 1]) + sig[1:]
        with FirstCallTimer() as timer:
            for size in sizes:
                want = np.arange(size) != size // 2
                sigs = [sig if ok else forged for ok in want]
                self._exe_report = None
                out = self.verify([msg] * size, [pk] * size, sigs)
                wrong = np.flatnonzero(out != want)
                if len(wrong):
                    raise RuntimeError(
                        f"verifier warmup at {size} lanes: wrong verdicts "
                        f"at lanes {wrong[:8].tolist()} (forged: {size // 2})"
                    )
                report = timer.take()
                if self._exe_report is not None:
                    report.update(self._exe_report)
                    if self._exe_report["exe"] == "loaded":
                        # out of the cache directory with no backend
                        # compile: what cache_hits exists to say
                        report.update(cache_hits=1, cache_misses=0)
                self.warm_report[size] = report

    def _neg_point(self, pk: bytes):
        hit = self._point_cache.get(pk)
        if hit is None and pk not in self._point_cache:
            p = ref.point_decompress(pk)
            hit = None if p is None else curve.point_to_limbs(ref.point_neg(p))
            with self._table_lock:
                self._point_cache[pk] = hit
                self._tables = None  # stacked table is stale
        return hit

    @property
    def donate_buffers(self) -> bool:
        """Donate the wave's device buffer to the jitted call (ISSUE 6)
        so XLA recycles its allocation across waves.  On by
        default on accelerator backends; ``HOTSTUFF_DONATE=1/0``
        forces either way (CPU jax has no donation support and warns
        once per shape, so it stays off there unless forced)."""
        if self._donate is None:
            import os

            env = os.environ.get("HOTSTUFF_DONATE", "").strip().lower()
            if env:
                self._donate = env not in ("0", "off", "no", "false")
            else:
                self._donate = jax.default_backend() in ("tpu", "gpu")
        return self._donate

    #: where the committee tables are placed (None: the default device);
    #: the mesh-sharded verifier replicates them over its mesh
    _table_sharding = None

    def _count(self, h2d: int, calls: int = 0) -> None:
        with self._count_lock:
            self.h2d += h2d
            self.calls += calls

    def device_counters(self) -> tuple[int, int]:
        """``(h2d, calls)``, as the verify service's stats line prints
        them (an optional capability: crypto/service.py)."""
        return self.h2d, self.calls

    def _device_build(self, build):
        """The device-resident copy of ``build``'s stacked tables,
        staged on first use after each rebuild.  Idempotent and safe
        without a lock: concurrent stagers both produce a valid copy of
        the same immutable build and last-write-wins."""
        if self._device_src is not build:
            tables, _ = build
            self._count(h2d=len(tables))
            self._device_tables = tuple(
                jax.device_put(t, self._table_sharding) for t in tables
            )
            self._device_src = build
        return self._device_tables

    def _scratch_for(self, rows: int) -> np.ndarray:
        """This thread's preallocated staging buffer for the pad shape
        ``rows`` rows land on, uint8 [padded, WAVE_COLS], every row reset
        to what a pad row carries: zero scalars, table row 0 (the zero
        dummy) and R = the identity's encoding (y = 1).  The kernel
        computes the identity on such a row and it passes, so the call
        needs no row count."""
        padded = next(p for p in self._padded_sizes() if p >= rows)
        pool = getattr(self._scratch, "pool", None)
        if pool is None:
            pool = self._scratch.pool = {}
        buf = pool.get(padded)
        if buf is None:
            buf = pool[padded] = np.zeros((padded, WAVE_COLS), np.uint8)
        else:
            buf.fill(0)
        buf[:, 0] = 1
        return buf

    def _rebuild_tables(self):
        """Build (tables, row_index) FULLY in locals, then publish with
        one atomic assignment — this object is shared across the event
        loop and the async verify service's worker thread, so a reader
        must never observe a partially-built index (a torn index maps a
        valid key to the zero row and an honest signature reports
        invalid).  Readers snapshot ``self._tables`` once and use only
        that build."""
        with self._table_lock:
            valid = [
                (pk, pt)
                for pk, pt in self._point_cache.items()
                if pt is not None
            ]
            # row 0 and the keys, up to a power of two from 128: the row
            # count is a shape of the jitted entry's argument, and a key
            # more (a stranger's signature) must not compile the kernel
            # again inside a wave's deadline
            k = max(128, 1 << len(valid).bit_length())
            tables = tuple(
                np.zeros((k, F.NLIMBS), np.int32) for _ in range(4)
            )
            row_index: dict[bytes, int] = {}
            for row, (pk, pt) in enumerate(valid, start=1):
                row_index[pk] = row
                for t, coord in zip(tables, pt):
                    t[row] = coord
            build = (tables, row_index)
            self._tables = build
            self._row_index = row_index
            return build

    def verify(
        self,
        messages: list[bytes],
        pubkeys: list[bytes],
        signatures: list[bytes],
    ) -> np.ndarray:
        """Per-item validity for distinct (message, pk, sig) triples."""
        n = len(messages)
        if not (n == len(pubkeys) == len(signatures)):
            raise ValueError("length mismatch")
        if n == 0:
            return np.zeros(0, bool)
        if n < self.min_device_batch:
            if self._cpu is None:
                from ..crypto.signature import batch_verify_arrays

                self._cpu = batch_verify_arrays
            with _spans.span("host.verify"):
                return np.asarray(self._cpu(messages, pubkeys, signatures))
        return self.verify_device(messages, pubkeys, signatures)

    def verify_device(
        self,
        messages: list[bytes],
        pubkeys: list[bytes],
        signatures: list[bytes],
    ) -> np.ndarray:
        """Per-item validity, forced onto the device kernel regardless of
        ``min_device_batch`` — for callers that already made the
        device-vs-CPU routing decision (the async verify service's
        adaptive dispatcher)."""
        n = len(messages)
        if n == 0:
            return np.zeros(0, bool)
        if n > self._padded_sizes()[-1]:
            # split oversized batches into max-shape chunks
            step = self._padded_sizes()[-1]
            return np.concatenate(
                [
                    self.verify_device(
                        messages[i : i + step],
                        pubkeys[i : i + step],
                        signatures[i : i + step],
                    )
                    for i in range(0, n, step)
                ]
            )

        with _spans.span("prepare"):
            _, args, valid_host = self.stage(messages, pubkeys, signatures)
        return self._dispatch(args, valid_host)

    def _dispatch(self, args, valid_host) -> np.ndarray:
        """A staged wave through the device, in the waterfall's stages.
        The fence is part of the production path (ISSUE 5): overlap
        happens at the WAVE level — the dispatch pipeline parks this
        worker thread in device.execute (GIL released) while the next
        wave stages on another thread.  The internal dispatch donates
        the wave's buffer when enabled (a per-wave temporary); external
        stage() users call the kernel with donate's default False."""
        with _spans.span("dispatch"):
            ok = self._run_wave(*args, donate=self.donate_buffers)
            # the verdicts follow the kernel to the host by themselves,
            # not on a round trip of their own after the fence
            ok.copy_to_host_async()
        with _spans.span("device.execute"):
            ok = jax.block_until_ready(ok)
        with _spans.span("readback"):
            return np.asarray(ok)[: len(valid_host)] & valid_host

    def verify_packed(self, dig_buf, pk_buf, sig_buf, rows: int) -> np.ndarray:
        """Zero-copy verify over adopted native ingest-arena columns
        (ISSUE 20): the ``*_buf`` objects expose the arena's digest /
        pk / sig column memory (buffer protocol), every one of ``rows``
        rows holding a well-formed claim (real votes + valid pad rows
        the native packer pre-filled).  Staging reads the columns
        through frombuffer views — no per-claim flatten, no ``b"".join``
        — and feeds the same jitted bucket callable as verify_device.
        The arena memory is never written; the caller owns its lifetime
        until this returns."""
        if rows == 0:
            return np.zeros(0, bool)
        dig_v = np.frombuffer(dig_buf, np.uint8).reshape(rows, 32)
        pk_v = np.frombuffer(pk_buf, np.uint8).reshape(rows, 32)
        sig_v = np.frombuffer(sig_buf, np.uint8).reshape(rows, 64)
        if rows > self._padded_sizes()[-1]:
            # oversize wave: materialize rows and chunk via verify_device
            return self.verify_device(
                [r.tobytes() for r in dig_v],
                [r.tobytes() for r in pk_v],
                [r.tobytes() for r in sig_v],
            )
        with _spans.span("prepare"):
            valid_host, args = self.prepare_packed(dig_v, pk_v, sig_v)
        return self._dispatch(args, valid_host)

    def prepare_packed(self, dig_v, pk_v, sig_v) -> tuple[np.ndarray, tuple]:
        """``prepare`` over arena column views: signature staging is ONE
        block copy off the column, and the wire parser already validated
        lengths, so the malformed-length scan is gone.  The per-row
        Python of ``_fill_rows`` needs hashable bytes keys; a native
        challenge-hash column (SHA-512 mod L in wave_pack.cpp) is the
        noted follow-up that would erase it."""
        n = dig_v.shape[0]
        buf = self._scratch_for(n)
        buf[:n, SIG_COLS] = sig_v  # one vectorized copy straight off the arena
        return self._fill_rows(
            buf,
            np.ones(n, dtype=bool),
            [r.tobytes() for r in dig_v],
            [r.tobytes() for r in pk_v],
            [r.tobytes() for r in sig_v],
        )

    def stage(self, messages, pubkeys, signatures):
        """(kernel_fn, its arguments, host_validity) for this batch —
        the production dispatch point (the mesh-sharded subclass
        overrides ``_run_wave``).

        NOTE (round 3): a split-scalar kernel variant (each signature as
        two 128-bit half rows, 16 macro steps) lived here through round
        2.  It was DELETED together with its 2^128-point caches, doubled
        base tables and interleave layout: its entire win was avoiding
        the old 256-lane minimum pad, and the kernel is VPU-throughput-
        bound (~linear cost in lanes, pre-chip rig),
        so with the 128-lane tile a 64-vote QC at 32 steps x 128 lanes
        costs the same as 16 steps x 256 lanes, without ~600 lines of
        machinery."""
        valid_host, args = self.prepare(messages, pubkeys, signatures)
        return self._run_wave, args, valid_host

    def prepare(
        self,
        messages: list[bytes],
        pubkeys: list[bytes],
        signatures: list[bytes],
    ) -> tuple[np.ndarray, tuple]:
        """Host-side batch preparation: the wave's one staging buffer,
        preallocated at the PADDED shape per worker thread and reused
        across waves (ISSUE 5), filled by one memset + block numpy ops
        and the per-row work of ``_fill_rows``.  Returns
        (host_validity[n], (device tables, buffer)): the arguments of
        ``_run_wave``.  Windows, limbs and the key gather are the jitted
        call's (``unpack_wave``)."""
        n = len(messages)
        buf = self._scratch_for(n)
        # malformed-length rejections (rare; everything else vectorizes)
        valid_host = np.array(
            [
                len(sig) == 64 and len(pk) == 32
                for sig, pk in zip(signatures, pubkeys)
            ],
            dtype=bool,
        )
        if valid_host.all():
            buf[:n, SIG_COLS] = np.frombuffer(
                b"".join(signatures), np.uint8
            ).reshape(n, 64)
        else:
            for i in np.flatnonzero(valid_host):
                buf[i, SIG_COLS] = np.frombuffer(signatures[i], np.uint8)
        return self._fill_rows(buf, valid_host, messages, pubkeys, signatures)

    def _fill_rows(self, buf, valid_host, messages, pubkeys, signatures):
        """The tail ``prepare`` and ``prepare_packed`` share, over a
        buffer that holds the signatures of the rows ``valid_host`` has
        not refused yet: the exact host-side checks (s < L, a key that
        decompresses to a point), each row's key index and challenge
        scalar.  The only per-item Python left is the cached key lookup
        and the SHA-512 challenge hash (no batch API on the host)."""
        n = len(valid_host)
        # s >= L rejection, vectorized: lexicographic compare of each
        # scalar (big-endian view of sig[32:]) against L; rows equal to
        # L have no differing byte and are rejected too
        s_be = buf[:n, 63:31:-1]
        diff = s_be != _L_BE
        any_diff = diff.any(axis=1)
        first = np.where(any_diff, diff.argmax(axis=1), 0)
        valid_host &= (s_be[np.arange(n), first] < _L_BE[first]) & any_diff

        # committee points: decompress any unseen key once (the cache
        # insert marks the stacked build stale), THEN snapshot one
        # build — it post-dates this batch's inserts, so row_of covers
        # every valid pk here even if another thread rebuilds
        # concurrently.  Row 0 is the zero dummy: refused items keep it.
        for i in np.flatnonzero(valid_host):
            if pubkeys[i] not in self._point_cache:
                self._neg_point(pubkeys[i])
        build = self._tables
        if build is None:
            build = self._rebuild_tables()
        row_of = build[1]
        row_col = buf[:, ROW_COLS].view("<u4")[:, 0]
        for i in np.flatnonzero(valid_host):
            row = row_of.get(pubkeys[i], 0)
            if row:
                row_col[i] = row
            else:
                valid_host[i] = False  # key decompresses to no point

        # challenge hashes: the irreducible per-item host work —
        # memoized, so fixed-shape pad lanes (same claim every wave)
        # and re-verified claims skip the SHA-512
        memo = self._challenge_memo
        for i in np.flatnonzero(valid_host):
            key = (signatures[i], pubkeys[i], messages[i])
            kb = memo.get(key)
            if kb is None:
                kb = ref.verify_challenge(*key).to_bytes(32, "little")
                if len(memo) >= 8192:
                    memo.clear()
                memo[key] = kb
            buf[i, K_COLS] = np.frombuffer(kb, np.uint8)
        bad = np.flatnonzero(~valid_host)
        if len(bad):
            # a refused row rides as a pad row: the lane passes on the
            # device and valid_host masks it out
            buf[bad] = 0
            buf[bad, 0] = 1
        return valid_host, (self._device_build(build), buf)

    def _run_wave(self, tables, buf, donate=False):
        """Buffer on the host -> verdicts on the device: one transfer,
        one compiled call.  The step the mesh-sharded verifier overrides
        (rows placed shard-aligned).  ``donate=True`` selects the
        buffer-donating compilation of the same entry; the host buffer
        is this thread's to refill once the verdicts are back."""
        self._count(h2d=1, calls=1)
        buf = jax.device_put(buf)
        return self._wave_program(tables, buf, donate)(tables, buf)

    def _wave_program(self, tables, buf, donate):
        """The program a wave of these shapes runs: the jitted entry
        itself, or where the executable store is engaged
        (``exe_store``) its compiled program for these shapes, loaded
        from disk or built once and kept there."""
        key = (donate, buf.shape, tables[0].shape)
        program = self._programs.get(key)
        if program is None:
            with self._program_lock:
                program = self._programs.get(key)
                if program is None:
                    program = self._programs[key] = self._load_or_build(
                        tables, buf, donate
                    )
        return program

    def _load_or_build(self, tables, buf, donate):
        entry = _wave_entry(self.use_pallas, donate)
        store = self.exe_store
        if store is None:
            return entry
        key = _exe_store.key(
            "ed25519.wave", (tables, buf), pallas=self.use_pallas, donate=donate
        )
        program, self._exe_report = store.get(
            key, lambda: entry.lower(tables, buf).compile()
        )
        return program

    @property
    def exe_store(self) -> "_exe_store.ExecutableStore | None":
        """Where compiled wave programs are kept across processes: the
        directory given to the constructor, else beside the compile
        cache on the Pallas entry (the TPU backend) only; None keeps the
        plain jitted entry."""
        if self._exe_store is None:
            root = self._exe_dir
            if root is None and self.use_pallas:
                root = _exe_store.default_dir()
            self._exe_store = _exe_store.ExecutableStore(root) if root else False
        return self._exe_store or None

    # -- VerifierBackend protocol (hotstuff_tpu.crypto.service) --------------

    name = "tpu"

    #: the async verify service may pre-pad device waves to fixed
    #: bucket shapes with always-valid filler claims (ISSUE 6) — real
    #: device verifiers opt in; synthetic test hosts never set this
    supports_wave_padding = True

    def verify_many(
        self,
        digests: list[bytes],
        pks: list[bytes],
        sigs: list[bytes],
        aggregate_ok: bool = False,
    ) -> list[bool]:
        # aggregate_ok is irrelevant for ed25519: verification is
        # per-signature on the device regardless
        return [bool(v) for v in self.verify(digests, pks, sigs)]

    def verify_one(self, digest, pk, sig) -> bool:
        return bool(
            self.verify([digest.to_bytes()], [pk.to_bytes()], [sig.to_bytes()])[0]
        )

    def verify_shared_msg(self, digest, votes) -> bool:
        msg = digest.to_bytes()
        out = self.verify(
            [msg] * len(votes),
            [pk.to_bytes() for pk, _ in votes],
            [sig.to_bytes() for _, sig in votes],
        )
        return bool(out.all())
