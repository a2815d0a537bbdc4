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

from ..tpu.ed25519 import BatchVerifier, wave_fn
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


# in_specs for (tables, buffer): the committee tables replicated, the
# wave's staging buffer sharded by rows, so each device decomposes and
# gathers exactly its own slice (tpu/ed25519.py ``unpack_wave``).
_IN_SPECS = (P(), P(DP_AXIS))


def _bad_count(ok):
    """The invalid lanes over the whole mesh: the one word crossing ICI."""
    return jax.lax.psum(jnp.sum(jnp.logical_not(ok).astype(jnp.int32)), DP_AXIS)


def make_sharded_verify(
    mesh: Mesh,
    pallas: bool = False,
    interpret: bool = False,
    donate: bool = False,
    psum_word: bool = False,
):
    """jitted ``(tables, buffer) -> bool[rows]`` verification with the
    wave's rows sharded over the mesh.  Rows must be a multiple of the
    mesh size (the driver pads).  Per shard it is the base verifier's
    ``wave_fn``: decomposition, key gather, kernel.

    ``pallas=True`` runs the fully fused Pallas kernel per shard (TPU
    meshes; per-shard rows must be a multiple of pallas_dsm.LANE_TILE,
    which the verifier's pad grid guarantees; the XLA kernel remains the
    portable path for the CPU-mesh tests and dryrun).  ``interpret=True``
    (tests only) drives the pallas branch through the interpreter so the
    exact production route (shard_map + Pallas + psum) gets multi-device
    parity coverage on the CPU test mesh (VERDICT r2 item 7).

    ``donate=True`` donates the wave's buffer, mirroring the base
    verifier's entry — the replicated committee tables are never
    donated.

    ``psum_word=True`` additionally returns the replicated invalid-count
    scalar — the single psum word crossing ICI that the paper's scaling
    story hinges on.  The production mesh readback fetches THAT word
    first and skips the multi-shard lane gather entirely when the whole
    wave is valid (the common case)."""
    local = wave_fn(pallas, interpret)
    if psum_word:
        inner = local

        def local(tables, buf):
            ok = inner(tables, buf)
            return ok, _bad_count(ok)

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
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def make_sharded_qc_check(mesh: Mesh):
    """jitted scalar-bool "is every signature in this QC valid" with the
    wave's rows sharded over the mesh and a single psum word crossing
    ICI."""
    local = wave_fn(pallas=False)
    fn = shard_map(
        lambda tables, buf: _bad_count(local(tables, buf)) == 0,
        mesh=mesh,
        in_specs=_IN_SPECS,
        out_specs=P(),
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
        # the compiled entry points by (psum_word, donate), each compiled
        # lazily per shape: stage() hands out the plain per-item kernel
        # like the base class; production verify_device dispatches the
        # psum-word variants (per-item lanes + the one ICI word).
        self._kernels = {
            (psum_word, donate): make_sharded_verify(
                self.mesh,
                pallas=self._shard_pallas,
                psum_word=psum_word,
                donate=donate,
            )
            for psum_word in (False, True)
            for donate in (False, True)
        }
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
        # tables replicate across the mesh once per rebuild (the base
        # class's _device_build places them by _table_sharding), each
        # wave ships only its staging buffer, rows sharded over dp, and
        # every device gathers its own slice's rows inside the call.
        self._row_sharding = NamedSharding(self.mesh, P(DP_AXIS))
        self._table_sharding = NamedSharding(self.mesh, P())

    @property
    def kernel_name(self) -> str:
        return "pallas" if self._shard_pallas else "xla"

    def _run_wave(self, tables, buf, donate=False, psum_word=False):
        """The base class's step with the placement changed: the
        buffer's rows land sharded over dp, so each device holds exactly
        its slice.  Donation hands the buffer back to XLA as the base
        entry does (ISSUE 7)."""
        self._count(h2d=1, calls=1)
        return self._kernels[psum_word, donate](
            tables, jax.device_put(buf, self._row_sharding)
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
        with _spans.span("prepare"):
            valid_host, args = self.prepare(messages, pubkeys, signatures)
        with _spans.span("dispatch"):
            ok, bad = self._run_wave(
                *args, donate=self.donate_buffers, psum_word=True
            )
        with _spans.span("device.execute"):
            ok = jax.block_until_ready(ok)
        with _spans.span("mesh.psum"):
            bad_count = int(np.asarray(bad))
        if bad_count == 0:
            # every lane passed, and a row the host refused rides as a
            # passing pad row: the host's verdicts are the wave's
            return valid_host
        with _spans.span("readback"):
            return np.asarray(ok)[:n] & valid_host
