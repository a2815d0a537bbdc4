"""Device-mesh sharding for the Ed25519 batch-verify kernel.

TPU-first design: the verification batch is embarrassingly parallel over
signatures, so the batch axis is sharded over the mesh's ``dp`` axis with
``shard_map`` — each chip runs the fused double-scalar-multiplication
scan on its slice with ZERO communication; only the final "is the whole
QC valid" bit is a one-word ``psum`` over ICI. This is the
committee-size scaling story for the BASELINE.json 256-node configs:
a 256-vote QC shards 32 signatures per chip on a v5e-8.

All functions work identically on a real TPU slice or on the virtual
8-device CPU mesh used in tests (conftest sets
``--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..tpu import curve
from ..tpu.ed25519 import BatchVerifier
from ..telemetry import spans as _spans

DP_AXIS = "dp"


def mesh_devices_from_env() -> int | None:
    """``HOTSTUFF_MESH_DEVICES`` as a positive device count, or None when
    unset/invalid (None means "use every visible device").  This is the
    env half of the node CLI's ``--mesh-devices`` bridge: it is read at
    backend materialization so run/run-many/deploy and the bench
    subprocesses all size the production mesh the same way."""
    raw = os.environ.get("HOTSTUFF_MESH_DEVICES", "").strip()
    if not raw:
        return None
    try:
        n = int(raw)
    except ValueError:
        return None
    return n if n > 0 else None


def default_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D data-parallel mesh over the first ``n_devices`` devices."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (DP_AXIS,))


# in_specs for (ax, ay, az, at, s_bits, k_bits, r_y, r_sign): batch axis is
# axis 0 everywhere except the bit-planes, where it is axis 1.
_IN_SPECS = (
    P(DP_AXIS),
    P(DP_AXIS),
    P(DP_AXIS),
    P(DP_AXIS),
    P(None, DP_AXIS),
    P(None, DP_AXIS),
    P(DP_AXIS),
    P(DP_AXIS),
)


def _local_verify(ax, ay, az, at, s_bits, k_bits, r_y, r_sign):
    p = curve.dual_scalar_mult(s_bits, k_bits, (ax, ay, az, at))
    return curve.compressed_equals(p, r_y, r_sign)


def _make_local_verify_pallas(interpret: bool = False):
    """Per-shard dispatch of the fully fused Pallas verify (scan +
    in-VMEM compressed-equality epilogue) — each device runs it on its
    slice; per-shard batch must be a multiple of pallas_dsm.LANE_TILE
    (the verifier's pad grid guarantees it).  ``interpret=True`` runs
    the SAME kernel through the Pallas interpreter so the exact
    production route (shard_map + Pallas + psum) gets multi-device
    parity coverage on the CPU test mesh (VERDICT r2 item 7)."""
    from ..tpu import pallas_dsm

    def local(ax, ay, az, at, s_bits, k_bits, r_y, r_sign):
        return pallas_dsm.verify_compressed(
            s_bits, k_bits, (ax, ay, az, at), r_y, r_sign,
            interpret=interpret,
        )

    return local


def make_sharded_verify(
    mesh: Mesh,
    pallas: bool = False,
    interpret: bool = False,
    donate: bool = False,
    psum_word: bool = False,
):
    """jitted [batch]-bool verification with the batch sharded over the
    mesh. Batch size must be a multiple of the mesh size (the driver pads).

    ``pallas=True`` runs the Pallas kernel per shard (TPU meshes; the
    XLA kernel remains the portable path for the CPU-mesh tests and
    dryrun).  ``interpret=True`` (tests only) drives the pallas branch
    through the interpreter on CPU meshes.

    ``donate=True`` donates the per-wave staging temporaries (args 4-7:
    s_bits, k_bits, r_y, r_sign) to the kernel, mirroring the base
    verifier's ``_verify_kernel_donated`` — the committee point rows
    (args 0-3) alias the sharded device key gather and must NOT be
    donated.

    ``psum_word=True`` additionally returns the replicated invalid-count
    scalar — the single psum word crossing ICI that the paper's scaling
    story hinges on.  The production mesh readback fetches THAT word
    first and skips the multi-shard lane gather entirely when the whole
    wave is valid (the common case)."""
    local = _make_local_verify_pallas(interpret) if pallas else _local_verify
    if psum_word:
        inner = local

        def local(ax, ay, az, at, s_bits, k_bits, r_y, r_sign):
            ok = inner(ax, ay, az, at, s_bits, k_bits, r_y, r_sign)
            bad = jax.lax.psum(
                jnp.sum(jnp.logical_not(ok).astype(jnp.int32)), DP_AXIS
            )
            return ok, bad

        out_specs = (P(DP_AXIS), P())
    else:
        out_specs = P(DP_AXIS)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=_IN_SPECS,
        out_specs=out_specs,
        # pallas_call's out_shape carries no varying-mesh-axes metadata,
        # so the vma consistency check cannot apply to the pallas branch
        check_vma=not pallas,
    )
    return jax.jit(fn, donate_argnums=(4, 5, 6, 7) if donate else ())


def make_sharded_qc_check(mesh: Mesh):
    """jitted scalar-bool "is every signature in this QC valid" with the
    batch sharded over the mesh and a single psum word crossing ICI."""

    def local_all(ax, ay, az, at, s_bits, k_bits, r_y, r_sign):
        ok = _local_verify(ax, ay, az, at, s_bits, k_bits, r_y, r_sign)
        bad = jax.lax.psum(jnp.sum(jnp.logical_not(ok).astype(jnp.int32)), DP_AXIS)
        return bad == 0

    fn = shard_map(
        local_all, mesh=mesh, in_specs=_IN_SPECS, out_specs=P()
    )
    return jax.jit(fn)


class ShardedBatchVerifier(BatchVerifier):
    """BatchVerifier whose kernel runs sharded over a device mesh.

    Host-side batch preparation (point-cache lookups, challenge hashing,
    padding) is inherited; only the device dispatch changes. Pads to a
    multiple of the mesh size on top of the power-of-4 shape grid so every
    chip gets an equal slice.
    """

    def __init__(self, mesh: Mesh | None = None, min_device_batch: int = 64):
        # use_pallas=False at the BASE-class routing level: the sharded
        # dispatch below owns kernel choice per shard instead (the base
        # class's split-kernel small-batch route assumes single-device
        # tile interleaving).
        super().__init__(min_device_batch=min_device_batch, use_pallas=False)
        self.mesh = mesh if mesh is not None else default_mesh()
        m = int(self.mesh.devices.size)
        # Per-shard Pallas on TPU meshes (each chip runs the fused
        # VMEM-resident scan on its slice — the v5e-8 path for the
        # <1 ms 256-vote QC target: 32 votes/chip in one lane tile);
        # XLA per shard on CPU meshes (tests/dryrun — Pallas has no CPU
        # lowering outside interpret mode).
        self._shard_pallas = (
            self.mesh.devices.flat[0].platform == "tpu"
        )
        mk = lambda **kw: make_sharded_verify(  # noqa: E731
            self.mesh, pallas=self._shard_pallas, **kw
        )
        # four compiled entry points, each compiled lazily per shape:
        # the plain per-item kernel keeps stage()/bench signature parity
        # with the base class; production verify_device dispatches the
        # psum-word variants (per-item lanes + the one ICI word).
        self._kernel = mk()
        self._kernel_donated = mk(donate=True)
        self._kernel_psum = mk(psum_word=True)
        self._kernel_psum_donated = mk(psum_word=True, donate=True)
        self.name = f"tpu-sharded-{m}"
        if self._shard_pallas:
            from ..tpu import pallas_dsm

            # Per-shard batches must be lane-tile multiples.  The grid
            # must include the intermediate multiples: (128, 128, 1024)
            # made a 256-vote QC pad to 1024 — 4x the work — which was
            # the whole "sharded route pays ~4x at mesh 1" anomaly
            # (sharded route 2.008 ms vs 0.526 single-device, pre-chip,
            # through the remote link).
            self.pad_sizes = tuple(
                m * k * pallas_dsm.LANE_TILE for k in (1, 2, 4, 8)
            )
        else:
            # equal per-device slices: powers of two from one row per
            # device up to 8192.  The old power-of-4 progression
            # (m * {1,4,16,64,...}) skipped 4096 at mesh 8 — a 4096-sig
            # train wave padded to 8192, 2x the work — and made every
            # canonical wave bucket land between grid points (bucket 64
            # at mesh 8 dispatched shape 128).  Powers of two keep each
            # bucket == its kernel shape at every mesh size.
            sizes, s = [], m
            while s <= 8192:
                sizes.append(s)
                s *= 2
            self.pad_sizes = tuple(sizes)
        # Mesh-multiple wave bucket shapes advertised to the async
        # service's fixed-shape dispatch path (ISSUE 7): the canonical bucket
        # ladder (incl. the 4096 train bucket) snapped UP to this mesh's
        # pad grid, so every padded wave IS a pre-compiled kernel shape
        # with equal per-device slices.  On TPU meshes this snaps to the
        # lane-tile grid (e.g. v5e-8 -> 1024/2048/4096).
        grid = self.pad_sizes
        snapped = (
            next((p for p in grid if p >= b), grid[-1])
            for b in (16, 64, 256, 1024, 4096)
        )
        self.wave_bucket_shapes = tuple(sorted(set(snapped)))
        # Per-shard device key table (ISSUE 6): the stacked committee
        # tables replicate across the mesh once per rebuild, each wave
        # ships only its [padded] row indices sharded over dp, and the
        # gather runs device-side producing rows already laid out for
        # the shard_map in_specs — the sharded backend stops restaging
        # 4x[padded,20] coordinate rows every wave.
        self._row_sharding = NamedSharding(self.mesh, P(DP_AXIS))
        self._table_sharding = NamedSharding(self.mesh, P())
        self._sharded_gather = jax.jit(
            lambda tables, idxs: tuple(t[idxs] for t in tables),
            out_shardings=(self._row_sharding,) * 4,
        )

    @property
    def kernel_name(self) -> str:
        return "pallas" if self._shard_pallas else "xla"

    # per-shard key table: the staged gather emits rows sharded to
    # match the shard_map in_specs (see _gather_device_rows), so the
    # PR 5 device key cache now applies to the mesh backend too
    device_key_cache = True

    def _device_build(self, build):
        """Replicate the stacked committee tables across the mesh once
        per rebuild (committee keys are epoch-static)."""
        if self._device_src is not build:
            tables, _ = build
            self._device_tables = tuple(
                jax.device_put(t, self._table_sharding) for t in tables
            )
            self._device_src = build
        return self._device_tables

    def _gather_device_rows(self, build, idxs):
        """Shard-aligned committee gather: [padded] indices sharded
        over dp index the replicated tables, so each device produces
        exactly its own slice of the coordinate rows."""
        tables = self._device_build(build)
        return self._sharded_gather(
            tables, jax.device_put(idxs, self._row_sharding)
        )

    def _run_kernel(
        self, ax, ay, az, at, s_bits, k_bits, r_y, r_sign, donate=False
    ):
        # donation wired through the shard_map jit (ISSUE 7): the
        # donated compilation hands the four per-wave staging
        # temporaries (bit-planes + R rows) back to XLA, exactly like
        # the base class's _verify_kernel_donated — the point rows stay
        # un-donated because they alias the sharded committee gather.
        kernel = self._kernel_donated if donate else self._kernel
        return kernel(
            jnp.asarray(ax),
            jnp.asarray(ay),
            jnp.asarray(az),
            jnp.asarray(at),
            jnp.asarray(s_bits),
            jnp.asarray(k_bits),
            jnp.asarray(r_y),
            jnp.asarray(r_sign),
        )

    def verify_device(self, messages, pubkeys, signatures):
        """Mesh dispatch with the psum-word readback: each wave returns
        the per-item lanes (sharded over dp) AND the replicated
        invalid-count scalar — the one word that crosses ICI.  The host
        blocks on compute, fetches that word, and only gathers the
        sharded lane array when something was actually invalid, so the
        common all-valid wave's readback is a single scalar transfer
        instead of a cross-shard gather.  Under the profiler the word
        fetch is its own ``mesh.psum`` span, sitting between
        device.execute and readback in the waterfall."""
        n = len(messages)
        if n == 0:
            return np.zeros(0, bool)
        if n > self._padded_sizes()[-1]:
            # oversized batches chunk through the base class, which
            # recurses back here per max-shape chunk
            return super().verify_device(messages, pubkeys, signatures)
        donate = self.donate_buffers
        kernel = self._kernel_psum_donated if donate else self._kernel_psum
        rec = _spans.recorder()
        if rec is None:
            valid_host, arrays = self.prepare(messages, pubkeys, signatures)
            ok, bad = kernel(*(jnp.asarray(a) for a in arrays))
            ok = jax.block_until_ready(ok)
            if int(np.asarray(bad)) == 0:
                # every lane valid => host validity was all-True too
                # (host-invalid rows are zeroed into failing lanes)
                return np.ones(n, bool)
            return np.asarray(ok)[:n] & valid_host
        with rec.span("prepare"):
            valid_host, arrays = self.prepare(messages, pubkeys, signatures)
        with rec.span("dispatch"):
            ok, bad = kernel(*(jnp.asarray(a) for a in arrays))
        with rec.span("device.execute"):
            ok = jax.block_until_ready(ok)
        with rec.span("mesh.psum"):
            bad_count = int(np.asarray(bad))
        if bad_count == 0:
            return np.ones(n, bool)
        with rec.span("readback"):
            return np.asarray(ok)[:n] & valid_host
