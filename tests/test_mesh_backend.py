"""Sharded-mesh production backend (ISSUE 7): padded-wave verdict
parity across virtual mesh sizes, the mesh-multiple bucket ladder, and
the shard-aligned committee gather surviving a rebuild.

All mesh sizes here run on the virtual 8-device CPU mesh (conftest sets
``--xla_force_host_platform_device_count=8``).
"""

import asyncio

import numpy as np
import pytest

from hotstuff_tpu.crypto import Digest, Signature, generate_keypair
from hotstuff_tpu.crypto.async_service import AsyncVerifyService
from hotstuff_tpu.crypto.service import CpuVerifier
from hotstuff_tpu.node.node import _DeviceDispatch
from hotstuff_tpu.parallel.mesh import ShardedBatchVerifier, default_mesh

from .common import async_test


def _claims(n: int, seed: int, tamper=frozenset()):
    """n single-sig claims over DISTINCT digests; tampered indices sign
    the wrong digest (a well-formed signature that must fail on the
    device lanes, not in host pre-validation)."""
    wrong = Digest(b"\xee" * 32)
    claims, pks = [], []
    for i in range(n):
        msg = bytes([seed, i]) + b"\x00" * 30
        pk, sk = generate_keypair(bytes([seed]) * 32, i)
        sig = Signature.new(wrong if i in tamper else Digest(msg), sk)
        claims.append(("one", msg, pk.to_bytes(), sig.to_bytes()))
        pks.append(pk.to_bytes())
    return claims, pks


class _MeshHost:
    """LazyDeviceVerifier stand-in holding a REAL ShardedBatchVerifier.

    The lazy host materializes ONE shared device per kind per process,
    so cross-mesh-size tests build the verifier explicitly and expose
    the same capability surface the service consults (async_kind names
    the mesh so the service labels its dispatches "mesh")."""

    supports_wave_padding = True
    device_ready = True
    dispatch_deadline_s = 30.0

    def __init__(self, mesh_size: int):
        self.device = ShardedBatchVerifier(
            mesh=default_mesh(mesh_size), min_device_batch=0
        )
        self.async_kind = f"mesh-{mesh_size}-test"
        self.name = self.async_kind
        self.cpu_backend = CpuVerifier()
        self.dispatched_batches: list[int] = []
        inner = _DeviceDispatch(self.device)
        host = self

        class _Counted:
            supports_wave_padding = True

            def verify_many(self, digests, pks, sigs, aggregate_ok=False):
                host.dispatched_batches.append(len(digests))
                return inner.verify_many(digests, pks, sigs, aggregate_ok)

        self.async_backend = _Counted()
        self.wave_bucket_shapes = self.device.wave_bucket_shapes

    def precompute(self, pks) -> None:
        self.device.precompute(pks)


@pytest.mark.parametrize("m", [2, 4, 8])
def test_bucket_shapes_are_mesh_multiples(m):
    """Every advertised wave bucket is a pad-grid entry (== a kernel
    shape) with equal per-device slices, and the 4096 train bucket
    exists at every mesh size."""
    v = ShardedBatchVerifier(mesh=default_mesh(m), min_device_batch=0)
    shapes = v.wave_bucket_shapes
    assert shapes == tuple(sorted(set(shapes)))
    assert all(b % m == 0 for b in shapes)
    assert set(shapes) <= set(v.pad_sizes)
    assert 4096 in shapes
    # the canonical ladder survives snapping on small meshes: the
    # smallest bucket stays small enough that a QC-16 wave is not
    # padded past 2x
    assert shapes[0] <= 16


def test_service_resolves_buckets_from_backend(monkeypatch):
    """Without an explicit HOTSTUFF_WAVE_BUCKETS the service adopts the
    mesh backend's advertised ladder; an explicit env still wins."""
    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    host = _MeshHost(2)
    service = AsyncVerifyService(host, device=True)
    try:
        assert service.wave_buckets == host.wave_bucket_shapes
        monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "8,32")
        assert service.wave_buckets == (8, 32)
    finally:
        service.close()


@pytest.mark.parametrize("m", [2, 4, 8])
@async_test
async def test_padded_wave_verdict_parity_across_mesh_sizes(m, monkeypatch):
    """One coalesced wave (two submitters, one tampered claim) through
    the production dispatch pipeline at each virtual mesh size: the
    wave pads to the mesh bucket, the pads stay valid through the
    sharded gather, the poisoned lane fails WITHOUT flipping its
    neighbors, and the claim table fans each submitter its own
    verdicts.  Dispatches carry the "mesh" route label."""
    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    host = _MeshHost(m)
    a_claims, a_pks = _claims(3, seed=0x51)
    b_claims, b_pks = _claims(2, seed=0x52, tamper={1})
    host.precompute(a_pks + b_pks)
    service = AsyncVerifyService(host, device=True)
    try:
        task_a = asyncio.ensure_future(service.verify_claims(a_claims))
        task_b = asyncio.ensure_future(service.verify_claims(b_claims))
        out_a, out_b = await asyncio.gather(task_a, task_b)
        # per-submitter fanout with poison isolation
        assert out_a == [True, True, True]
        assert out_b == [True, False]
        # both submissions coalesced into ONE padded mesh dispatch at
        # the smallest bucket (5 real sigs -> bucket 16)
        assert host.dispatched_batches == [16]
        assert service.packed_waves == 1
        assert service.pad_sigs == 11
        # the dispatch rode the pipelined device path under the mesh
        # route label — no CPU spill, no unpadded fallback
        assert service.device_dispatches == 1
        assert service.mesh_dispatches == 1
        assert service.cpu_dispatches == 0
        assert service.peak_inflight <= service.pipeline_depth
    finally:
        service.close()


def test_sharded_gather_matches_in_specs_after_rebuild():
    """After a committee REBUILD the staged tables are still replicated
    over the mesh and the wave's buffer still lands with its rows
    sharded to match the shard_map in_specs (P('dp') on the batch axis),
    so each device gathers its own slice; the gathered coordinate rows
    are numerically identical to the single-device verifier's rows for
    the new committee."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hotstuff_tpu.tpu.ed25519 import BatchVerifier

    def batch(seed):
        shared = Digest.of(bytes([seed]) * 16)
        msgs, pks, sigs = [], [], []
        for i in range(16):
            pk, sk = generate_keypair(bytes([seed]) * 32, i)
            msgs.append(shared.to_bytes())
            pks.append(pk.to_bytes())
            sigs.append(Signature.new(shared, sk).to_bytes())
        return msgs, pks, sigs

    v = ShardedBatchVerifier(mesh=default_mesh(4), min_device_batch=0)
    msgs_a, pks_a, sigs_a = batch(0x61)
    v.precompute(pks_a)
    _, (tables_a, _) = v.prepare(msgs_a, pks_a, sigs_a)  # committee A's tables

    # rebuild: a NEW committee replaces the device-resident tables
    msgs_b, pks_b, sigs_b = batch(0x62)
    v.precompute(pks_b)
    valid_host, (tables, buf) = v.prepare(msgs_b, pks_b, sigs_b)
    assert valid_host.all() and tables is not tables_a

    replicated = NamedSharding(v.mesh, P())
    for table in tables:
        assert table.sharding.is_equivalent_to(replicated, table.ndim)
    # the production entry (the psum word beside the lanes)
    ok, bad = v._run_wave(tables, buf, psum_word=True)
    want = NamedSharding(v.mesh, P("dp"))
    assert ok.sharding.is_equivalent_to(want, ok.ndim)
    assert np.asarray(ok).all() and int(bad) == 0

    # numeric parity with the single-device verifier for the same
    # committee/batch (same 16-entry padded shape on both grids): the
    # staged buffers alike, and the operands unpacked from them
    from hotstuff_tpu.tpu.ed25519 import unpack_wave

    base = BatchVerifier(min_device_batch=0, use_pallas=False)
    base.precompute(pks_a + pks_b)
    _, (base_tables, base_buf) = base.prepare(msgs_b, pks_b, sigs_b)
    np.testing.assert_array_equal(buf, base_buf)
    unpack = jax.jit(unpack_wave)
    arrays = unpack(tables, jax.device_put(buf, want))
    for got, ref in zip(arrays, unpack(base_tables, base_buf)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
