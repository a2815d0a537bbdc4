"""Node: the composition root wiring store, crypto, and consensus.

Parity target: reference ``Node`` (node/src/node.rs:16-65): read the
committee/secret/parameters files, open the store, start the signature
service, spawn Consensus, and expose (and optionally drain) the commit
channel.

TPU addition: ``verifier_backend`` selects where signature batches are
verified — "cpu" (default) or "tpu" (the JAX batch kernel,
hotstuff_tpu/tpu/ed25519.py) — the SignatureService-boundary plug point
from BASELINE.json.
"""

from __future__ import annotations

import asyncio
import os
import logging
import time

from ..consensus import Consensus, Parameters
from ..crypto.scheme import (
    make_cpu_verifier,
    make_device_verifier,
    make_signing_service,
)
from ..crypto.service import CpuVerifier, VerifierBackend
from ..network.wan import WanSpecError
from ..store import Store
from .config import ConfigError, Secret, read_committee, read_parameters

log = logging.getLogger(__name__)

#: provenance tag on persisted state: the hash of the committee the
#: store's consensus/state records were produced under.  Disjoint from
#: every other store namespace (32-byte digests, 8-byte round keys,
#: ``consensus_state``, ``latest_round``, ``p<digest>``, ``s/...``).
COMMITTEE_HASH_KEY = b"committee_hash"


def committee_hash(committee) -> bytes:
    """Canonical identity of a committee (or schedule): the digest of
    its sorted-key JSON form — the same serialization the config files
    carry, so identical files hash identically across nodes."""
    import json

    from ..crypto.digest import sha512_trunc

    return sha512_trunc(
        json.dumps(committee.to_json(), sort_keys=True).encode()
    )


class _DeviceDispatch:
    """Forced-device view of a shared BatchVerifier for the async verify
    service (crypto/async_service.py): the service makes the
    device-vs-CPU routing decision itself, so this view must never
    silently re-route a batch back to the host the way the hybrid
    ``verify_many`` would.  One instance per device kind, process-wide —
    its identity is the coalescing key: every in-process core's claims
    land in the same dispatch stream."""

    def __init__(self, device):
        self._device = device
        self.name = getattr(device, "name", "tpu")
        # forward the fixed-shape padding capability (ISSUE 6): the
        # async service pads device waves only when the real verifier
        # behind this view opted in
        self.supports_wave_padding = getattr(
            device, "supports_wave_padding", False
        )

    def verify_many(
        self, digests, pks, sigs, aggregate_ok: bool = False
    ) -> list:
        return [bool(v) for v in self._device.verify_device(digests, pks, sigs)]


class LazyDeviceVerifier:
    """Defers the jax/numpy import (seconds of interpreter time per node
    process, serialized across a co-located committee sharing few cores)
    until a batch is actually big enough for the device.  Small batches
    route to the CPU backend exactly like the device verifier's own
    hybrid routing, so committees whose batches never reach
    ``min_device_batch`` boot and run without ever importing jax.

    The materialized device verifier is shared per kind, process-wide:
    an in-process committee holds ONE point cache and ONE compiled
    kernel set, and the async verify service (``async_backend``)
    coalesces every core's claims into one dispatch stream."""

    min_device_batch = 64

    # both lazy kinds ("tpu", "tpu-sharded") materialize ed25519
    # BatchVerifiers, which accept fixed-shape wave padding (ISSUE 6)
    supports_wave_padding = True

    _shared_device: dict[str, VerifierBackend] = {}
    _shared_dispatch: dict[str, _DeviceDispatch] = {}
    # kinds whose device kernel has been warmed (compiled/cache-loaded)
    # in THIS process — the async service routes to the device only then
    _warm: set[str] = set()

    #: "mesh" is the user-facing spelling of the sharded backend
    #: (benchmark profile --verifier mesh, node --verifier mesh); it
    #: normalizes to the canonical kind at construction so both names
    #: share the same process-wide device singleton and warm state
    _KIND_ALIASES = {"mesh": "tpu-sharded"}

    def __init__(self, kind: str):
        kind = self._KIND_ALIASES.get(kind, kind)
        self._kind = kind
        self._cpu = CpuVerifier()
        self._precomputed: list[bytes] = []
        self.name = kind
        # Advertises the async off-loop claim path to AsyncVerifyService
        # (one coalescing service per kind per loop).
        self.async_kind = kind

    @property
    def cpu_backend(self) -> CpuVerifier:
        return self._cpu

    @property
    def device_ready(self) -> bool:
        """True once the device kernel is warm — the async service must
        never trigger a cold jax import or Mosaic compile mid-consensus."""
        return self._kind in self._warm

    @property
    def _device(self) -> VerifierBackend | None:
        return self._shared_device.get(self._kind)

    def device_counters(self) -> tuple[int, int]:
        """The device verifier's ``(h2d, calls)`` for the verify
        service's stats line; zeros until the device materializes."""
        device = self._device
        return (0, 0) if device is None else device.device_counters()

    @property
    def wave_bucket_shapes(self) -> tuple | None:
        """The device verifier's advertised wave bucket ladder (the mesh
        backend's mesh-multiple shapes, ISSUE 7) — None until the device
        materializes, so the async service's lazy bucket resolution
        falls back to the canonical ladder before warmup and picks the
        mesh grid up the moment it exists."""
        device = self._device
        if device is None:
            return None
        return getattr(device, "wave_bucket_shapes", None)

    def _materialize(self) -> VerifierBackend:
        device = self._shared_device.get(self._kind)
        if device is None:
            if self._kind == "tpu":
                from ..tpu.ed25519 import BatchVerifier

                device = BatchVerifier(min_device_batch=self.min_device_batch)
            else:  # tpu-sharded: batch sharded over the device mesh
                from ..parallel.mesh import (
                    ShardedBatchVerifier,
                    default_mesh,
                    mesh_devices_from_env,
                )

                # HOTSTUFF_MESH_DEVICES (node --mesh-devices) sizes the
                # production mesh; unset means every visible device.
                # Read HERE, at materialization, because that is the
                # moment the mesh is actually built — the CLI bridge
                # sets the env before any verifier exists.
                n = mesh_devices_from_env()
                device = ShardedBatchVerifier(
                    mesh=default_mesh(n) if n else None,
                    min_device_batch=self.min_device_batch,
                )
            self._shared_device[self._kind] = device
        if self._precomputed:
            device.precompute(self._precomputed)
            self._precomputed = []
        return device

    @property
    def async_backend(self) -> _DeviceDispatch:
        """The shared forced-device dispatch view (one per kind) the
        async verify service coalesces on."""
        dispatch = self._shared_dispatch.get(self._kind)
        if dispatch is None:
            dispatch = _DeviceDispatch(self._materialize())
            self._shared_dispatch[self._kind] = dispatch
        return dispatch

    def precompute(self, pubkeys: list[bytes]) -> None:
        self._precomputed = list(pubkeys)
        if self._device is not None:
            self._device.precompute(pubkeys)

    def warmup(self, batch: int | None = None) -> None:
        if self._kind in self._warm:
            return  # the shared device instance is already warm
        import json
        import time

        t0 = time.perf_counter()
        device = self._materialize()
        device.warmup(batch)
        self._warm.add(self._kind)
        # NOTE: this log entry is part of the benchmark log-scrape
        # contract (chip_smoke.py reads the device, the kernel and the
        # cache hits per pad shape from it).
        log.info(
            "Device verifier [%s] warm in %.1f s: %s",
            self._kind,
            time.perf_counter() - t0,
            json.dumps(device.describe()),
        )

    def verify_one(self, digest, pk, sig) -> bool:
        return self._cpu.verify_one(digest, pk, sig)

    def verify_shared_msg(self, digest, votes) -> bool:
        if len(votes) < self.min_device_batch:
            return self._cpu.verify_shared_msg(digest, votes)
        return self._materialize().verify_shared_msg(digest, votes)

    def verify_many(
        self, digests, pks, sigs, aggregate_ok: bool = False
    ) -> list[bool]:
        if len(digests) < self.min_device_batch:
            return self._cpu.verify_many(digests, pks, sigs)
        return self._materialize().verify_many(
            digests, pks, sigs, aggregate_ok=aggregate_ok
        )


def make_verifier(kind: str, scheme: str = "ed25519") -> VerifierBackend:
    if kind == "cpu":
        return make_cpu_verifier(scheme)
    if kind in ("tpu", "tpu-sharded", "mesh"):
        if scheme == "bls":
            # BLS device path: each QC's running sum of vote signatures
            # on one device (hotstuff_tpu/tpu/bls.py), host pairing
            # equality per QC; the sharded kinds are refused
            return make_device_verifier(
                scheme, "tpu-sharded" if kind == "mesh" else kind
            )
        return LazyDeviceVerifier(kind)
    raise ValueError(f"unknown verifier backend '{kind}'")


class Node:
    CHANNEL_CAPACITY = 1_000
    #: seconds the process's nodes spent in their verifiers' warm-up
    #: (``node/main.py``'s ``Boot stats:`` line)
    warm_s = 0.0

    def __init__(self):
        self.commit: asyncio.Queue | None = None
        self.consensus: Consensus | None = None
        self.store: Store | None = None

    @classmethod
    async def new(
        cls,
        committee_file: str,
        key_file: str,
        store_path: str,
        parameters_file: str | None = None,
        verifier_backend: str = "cpu",
        bind_host: str = "0.0.0.0",
        transport: str = "asyncio",
    ) -> "Node":
        self = cls()
        committee = read_committee(committee_file)
        # Live reconfiguration (docs/RECONFIG.md) needs a spliceable
        # schedule: a bare committee file is promoted to a
        # single-entry schedule so a committed epoch change can extend
        # it at runtime.  for_round keeps every consumer oblivious.
        if not hasattr(committee, "splice"):
            from ..consensus.config import CommitteeSchedule

            committee = CommitteeSchedule([(1, committee)])
        secret = Secret.read(key_file)
        schemes = {c.scheme for c in committee.committees()}
        if len(schemes) == 1:
            if secret.scheme != next(iter(schemes)):
                raise ConfigError(
                    f"key file scheme '{secret.scheme}' does not match the "
                    f"committee scheme '{next(iter(schemes))}'"
                )
        else:
            # Mixed-scheme schedule (scheme changeover at an epoch
            # boundary): identities are per-scheme — this node signs
            # under its own key's scheme and must be a member of at
            # least one epoch using it; verification must handle BOTH
            # schemes (old-epoch certificates keep verifying after the
            # changeover), so the verifier is the dual router.
            my_epochs = [
                c for c in committee.committees()
                if secret.name in c.authorities
            ]
            if not my_epochs:
                raise ConfigError(
                    "key is not a member of any epoch in the schedule"
                )
            if any(c.scheme != secret.scheme for c in my_epochs):
                raise ConfigError(
                    f"key file scheme '{secret.scheme}' does not match an "
                    "epoch this key belongs to"
                )
        parameters = (
            read_parameters(parameters_file) if parameters_file else Parameters()
        )

        self.store = Store(store_path, node=str(secret.name)[:8])
        # Committee-hash provenance: persisted consensus/execution state
        # is only valid under the committee that produced it.  A store
        # carrying another committee's history (the testbed's recycled
        # .db_* paths — the "fresh deploy recovers to round ~800" class)
        # is rejected EXPLICITLY and discarded, which is what makes the
        # old boot-time blanket wipe unnecessary on the happy path.
        # HOTSTUFF_FRESH_STATE=1 (--fresh-state) stays as the escape
        # hatch to force a clean slate regardless of provenance.
        #
        # The hash anchors on the GENESIS-era committee only: under live
        # reconfiguration the on-disk file stays the genesis artifact
        # while the store's schedule legitimately evolves past it — the
        # evolution itself is re-proven at boot from the certified
        # schedule links persisted at each commit (verified-successor
        # acceptance, below), not trusted from the provenance tag.
        chash = committee_hash(committee.committees()[0])
        # lint: allow(no-blocking-in-async) -- one-time boot path: the
        # node serves no traffic until new() returns, so a synchronous
        # engine read cannot stall a live round
        stored_hash = self.store.engine.get(COMMITTEE_HASH_KEY)
        fresh = os.environ.get("HOTSTUFF_FRESH_STATE", "") not in ("", "0")
        if fresh or (stored_hash is not None and stored_hash != chash):
            if fresh:
                log.info("Discarding persisted state (--fresh-state)")
            else:
                log.warning(
                    "Rejecting persisted state from a different committee "
                    "(stored %s, ours %s): starting fresh",
                    stored_hash.hex()[:16],
                    chash.hex()[:16],
                )
            self.store.close()
            import shutil

            shutil.rmtree(store_path, ignore_errors=True)
            self.store = Store(store_path, node=str(secret.name)[:8])
        # lint: allow(no-blocking-in-async) -- same one-time boot path
        self.store.engine.put(COMMITTEE_HASH_KEY, chash)
        signature_service = make_signing_service(secret.scheme, secret.secret)
        if len(schemes) == 1:
            verifier = make_verifier(verifier_backend, next(iter(schemes)))
        else:
            from ..crypto.scheme import make_dual_verifier

            verifier = make_dual_verifier(
                lambda s: make_verifier(verifier_backend, s)
            )
        # Verified-successor acceptance: replay the certified schedule
        # links a previous process lifetime persisted (core commit path,
        # SCHEDULE_LINKS_KEY) so a restart resumes with the same epoch
        # schedule it shut down with — each link is re-verified against
        # the schedule as extended so far, never trusted from disk.
        from ..consensus.core import SCHEDULE_LINKS_KEY
        from ..consensus.reconfig import splice_schedule_links
        from ..consensus.wire import decode_schedule_links

        # lint: allow(no-blocking-in-async) -- same one-time boot path
        raw_links = self.store.engine.get(SCHEDULE_LINKS_KEY)
        if raw_links:
            from ..consensus.errors import InvalidReconfig
            from ..utils.codec import CodecError

            try:
                n = splice_schedule_links(
                    decode_schedule_links(raw_links),
                    committee,
                    verifier,
                    log=log,
                )
                if n:
                    log.info(
                        "Replayed %d certified schedule links from the "
                        "store (newest epoch %d)",
                        n,
                        max(c.epoch for c in committee.committees()),
                    )
            except (CodecError, InvalidReconfig) as e:
                log.warning("Ignoring persisted schedule links: %s", e)
        if hasattr(verifier, "precompute"):
            # warm the TPU backend's committee point cache (epoch setup)
            verifier.precompute(
                [pk.to_bytes() for pk in committee.authorities]
            )
        committee_size = len(committee.authorities)
        # Nodes co-located in this process (run-many sets the hint): their
        # verification claims coalesce into ONE dispatch stream, so the
        # device pays off far below the per-node min_device_batch and the
        # warm shapes must cover whole-committee waves.
        colocated = int(os.environ.get("HOTSTUFF_COLOCATED_NODES", "1") or 1)
        if verifier_backend != "cpu" and hasattr(verifier, "warmup"):
            min_batch = getattr(verifier, "min_device_batch", 0)
            if committee_size >= min_batch or colocated > 1:
                # A device verifier means the chip: refuse any other
                # backend HERE, where the device is about to be engaged,
                # rather than carry on with the XLA kernel on the CPU
                # (committees that never get here never import jax).
                from ..tpu import require_tpu

                try:
                    require_tpu(f"--verifier {verifier_backend}")
                except RuntimeError as e:
                    raise ConfigError(str(e)) from e
                # compile/cache-load the device kernel BEFORE binding
                # the consensus port: a cold compile on the first QC
                # verify would stall past the round timeout and trigger
                # view changes (clients wait for the port, so boot-time
                # cost is invisible to the measured window).
                quorum = committee_size * 2 // 3 + 1
                wave = (
                    committee_size
                    if colocated <= 1
                    else min(1024, colocated * (quorum + 2))
                )
                began = time.perf_counter()
                verifier.warmup(batch=wave)
                Node.warm_s += time.perf_counter() - began
            else:
                # every possible batch (<= committee size) routes to the
                # CPU hybrid path: the kernel is never dispatched
                log.info(
                    "Device verifier [%s] selected but will never "
                    "engage: a committee of %d is below "
                    "min_device_batch=%d and this node is not "
                    "co-located; every batch verifies on the CPU",
                    verifier_backend,
                    committee_size,
                    min_batch,
                )

        from .. import telemetry

        tel = telemetry.for_node(str(secret.name)[:8])
        # Flight recorder (telemetry/journal.py): must attach BEFORE
        # Consensus.spawn — the consensus actors capture
        # ``telemetry.journal`` at construction time.
        self._journal = None
        jdir = telemetry.journal_dir(store_path)
        if tel is not None and jdir:
            from ..telemetry.journal import Journal

            self._journal = Journal(tel.node, jdir)
            tel.attach_journal(self._journal)
            if telemetry.spans.enabled():
                # verify-pipeline spans render as one per-process track
                # in the merged trace (first journaled node wins)
                telemetry.spans.attach_journal(self._journal)
            log.info("Flight recorder journaling to %s", jdir)
        if tel is not None:
            # per-node work accounting for the committee-scaling
            # decomposition: the counted verifier feeds the snapshot
            # document's verify keys; its loop-lag keys come from the
            # process's Host stats probe (telemetry/hoststats.py)
            from ..crypto.service import CountingVerifier, VerifyWork

            work = VerifyWork()
            verifier = CountingVerifier(verifier, work)
            tel.attach_verify_work(work)

        self.commit = asyncio.Queue(maxsize=self.CHANNEL_CAPACITY)
        try:
            self.consensus = await Consensus.spawn(
                secret.name,
                committee,
                parameters,
                signature_service,
                self.store,
                self.commit,
                verifier=verifier,
                bind_host=bind_host,
                transport=transport,
                telemetry=tel,
            )
        except WanSpecError as e:
            raise ConfigError(str(e)) from e
        self._snapshot_task = None
        if tel is not None:
            from ..telemetry.exporter import run_snapshot_logger

            self._snapshot_task = asyncio.ensure_future(
                run_snapshot_logger(
                    tel, logging.getLogger(f"telemetry.{secret.name}")
                )
            )
        self._health_task = None
        self._health_monitor = None
        if tel is not None and telemetry.health_enabled():
            from ..telemetry.health import CAMPAIGN_SUFFIX, HealthMonitor

            # campaign ring persists beside the journal (when journaling
            # is on) as <node>-campaign.json — a name the journal
            # loader's *.jsonl glob never matches
            campaign_path = (
                os.path.join(jdir, f"{tel.node}{CAMPAIGN_SUFFIX}")
                if jdir
                else None
            )
            from ..telemetry.critpath import rolling_attribution

            self._health_monitor = HealthMonitor(
                tel,
                tel.node,
                timeout_s=parameters.timeout_delay / 1000.0,
                campaign_path=campaign_path,
                logger=logging.getLogger(f"health.{secret.name}"),
                # rolling critical-path attribution over the node's own
                # trace ring (health.py is import-free, so the engine
                # hook is injected here)
                attribution_fn=lambda t=tel: rolling_attribution(
                    t.trace.recent(64)
                ),
            )
            self._health_task = asyncio.ensure_future(
                self._health_monitor.run()
            )
            # the live watch scrapes node-local incidents out of the
            # snapshot: the node's own monitor sees a commit stall a
            # fleet-side detector could only infer
            tel.add_section(
                "health",
                lambda m=self._health_monitor: {
                    "open": sorted(i.kind for i in m.open_incidents()),
                    **(
                        {
                            "dominant_stage": m.last_attribution.get(
                                "dominant", ""
                            ),
                            "regime": m.last_attribution.get("regime", ""),
                        }
                        if m.last_attribution
                        else {}
                    ),
                },
            )
            log.info("Health monitor running for node %s", tel.node)
        log.info("Node %s successfully booted", secret.name)
        return self

    async def analyze_block(self) -> None:
        """Drain the commit channel — the application layer stub
        (node/src/node.rs:61-65)."""
        while True:
            _block = await self.commit.get()
            # Here the application would execute the committed payload.

    async def serve(self) -> None:
        """Drain commits until the core retires — a committed epoch
        change excluded this node and its grace window elapsed
        (docs/RECONFIG.md) — then linger briefly so straggling peers can
        still fetch boundary certificates and snapshots, and shut down
        cleanly.  Nodes that are never voted out serve forever."""
        drain = asyncio.ensure_future(self.analyze_block())
        try:
            core = self.consensus.core
            while not getattr(core, "retired", False):
                await asyncio.sleep(0.5)
            linger = float(
                os.environ.get("HOTSTUFF_RECONFIG_LINGER_S", "5") or 5
            )
            log.info(
                "Core retired; lingering %.1f s for boundary sync "
                "before shutdown",
                linger,
            )
            await asyncio.sleep(linger)
        finally:
            drain.cancel()
        await self.shutdown()
        log.info("Node retired cleanly")

    async def shutdown(self) -> None:
        for attr in ("_snapshot_task", "_health_task"):
            task = getattr(self, attr, None)
            if task is not None:
                task.cancel()
        monitor = getattr(self, "_health_monitor", None)
        if monitor is not None:
            monitor.close()
        if self.consensus is not None:
            await self.consensus.shutdown()
        journal = getattr(self, "_journal", None)
        if journal is not None:
            journal.close()
        if self.store is not None:
            self.store.close()
