"""Async coalescing verification service + burst preverification tests
(VERDICT r3 item 1: QC/TC verification off the consensus critical path).
"""

import asyncio

import pytest

from hotstuff_tpu.crypto import Digest, Signature, generate_keypair
from hotstuff_tpu.crypto.async_service import (
    AsyncVerifyService,
    eval_claims_sync,
    flatten_claims,
)
from hotstuff_tpu.crypto.service import CpuVerifier

from .common import async_test


def _signed(seed: int, msg: bytes):
    """(pk, signature over the 32-byte msg treated as a digest)."""
    pk, sk = generate_keypair(bytes([seed]) * 32, 0)
    return pk, Signature.new(Digest(msg), sk)


def test_flatten_claims_spans():
    d1, d2 = b"\x01" * 32, b"\x02" * 32
    claims = [
        ("one", d1, b"pk1", b"s1"),
        ("shared", d2, ((b"pk2", b"s2"), (b"pk3", b"s3"))),
        ("one", d1, b"pk4", b"s4"),
    ]
    digests, pks, sigs, spans = flatten_claims(claims)
    assert digests == [d1, d2, d2, d1]
    assert pks == [b"pk1", b"pk2", b"pk3", b"pk4"]
    assert spans == [(0, 1), (1, 3), (3, 4)]


def test_eval_claims_mixed_validity():
    msg = b"m" * 32
    pk1, sig1 = _signed(1, msg)
    pk2, sig2 = _signed(2, msg)
    pk3, sig3 = _signed(3, msg)
    good_shared = (
        "shared",
        msg,
        (
            (pk1.to_bytes(), sig1.to_bytes()),
            (pk2.to_bytes(), sig2.to_bytes()),
        ),
    )
    bad_shared = (
        "shared",
        msg,
        (
            (pk1.to_bytes(), sig1.to_bytes()),
            (pk2.to_bytes(), sig1.to_bytes()),  # wrong sig for pk2
        ),
    )
    good_one = ("one", msg, pk3.to_bytes(), sig3.to_bytes())
    bad_one = ("one", msg, pk3.to_bytes(), sig1.to_bytes())
    out = eval_claims_sync(
        CpuVerifier(), [good_shared, bad_shared, good_one, bad_one]
    )
    assert out == [True, False, True, False]


def test_eval_claims_aggregate_preferring_backend():
    """prefers_aggregate backends see shared claims via verify_shared_msg
    (the BLS one-pairing path), singles via verify_many."""

    class Agg(CpuVerifier):
        prefers_aggregate = True
        shared_calls = 0
        many_calls = 0

        def verify_shared_msg(self, d, votes):
            Agg.shared_calls += 1
            return super().verify_shared_msg(d, votes)

        def verify_many(self, d, p, s, aggregate_ok=False):
            Agg.many_calls += 1
            return super().verify_many(d, p, s)

    msg = b"n" * 32
    pk1, sig1 = _signed(4, msg)
    pk2, sig2 = _signed(5, msg)
    claims = [
        ("shared", msg, ((pk1.to_bytes(), sig1.to_bytes()),
                         (pk2.to_bytes(), sig2.to_bytes()))),
        ("one", msg, pk1.to_bytes(), sig1.to_bytes()),
        ("one", msg, pk2.to_bytes(), sig1.to_bytes()),  # invalid
    ]
    out = eval_claims_sync(Agg(), claims)
    assert out == [True, True, False]
    assert Agg.shared_calls == 1
    assert Agg.many_calls == 1  # both singles in one batch


@async_test
async def test_inline_service_is_synchronous():
    msg = b"q" * 32
    pk, sig = _signed(6, msg)
    service = AsyncVerifyService.for_backend(CpuVerifier())
    assert not service.device
    out = await service.verify_claims(
        [("one", msg, pk.to_bytes(), sig.to_bytes())]
    )
    assert out == [True]


class _FakeDeviceHost:
    """A device host whose 'device' counts dispatches and records batch
    sizes — stands in for node.LazyDeviceVerifier + BatchVerifier."""

    def __init__(self, kind="fake", ready=True, delay=0.0):
        self.async_kind = kind
        self._ready = ready
        self.cpu_backend = CpuVerifier()
        self.dispatched_batches = []
        self._delay = delay
        host = self

        class _Dispatch:
            def verify_many(self, digests, pks, sigs, aggregate_ok=False):
                host.dispatched_batches.append(len(digests))
                if host._delay:
                    import time

                    time.sleep(host._delay)
                return CpuVerifier().verify_many(digests, pks, sigs)

        self.async_backend = _Dispatch()

    @property
    def device_ready(self):
        return self._ready


@async_test
async def test_device_service_coalesces_concurrent_submissions():
    """Claims submitted by many tasks in the same wave ride ONE device
    dispatch — the in-process committee coalescing that amortizes the
    dispatch latency."""
    msg = b"w" * 32
    pairs = [_signed(10 + i, msg) for i in range(8)]
    host = _FakeDeviceHost(kind="coalesce-test")
    service = AsyncVerifyService.for_backend(host)
    assert service.device

    async def submit(pk, sig):
        return await service.verify_claims(
            [("one", msg, pk.to_bytes(), sig.to_bytes())]
        )

    outs = await asyncio.gather(*(submit(pk, sig) for pk, sig in pairs))
    assert all(o == [True] for o in outs)
    # every submission coalesced into one batch of 8
    assert host.dispatched_batches == [8]
    service.close()


@async_test
async def test_device_service_gates_on_readiness():
    """A device that is not warm must never be dispatched to (cold
    compile mid-consensus) — claims route to the CPU backend."""
    msg = b"r" * 32
    pk, sig = _signed(30, msg)
    host = _FakeDeviceHost(kind="gate-test", ready=False)
    service = AsyncVerifyService.for_backend(host)
    out = await service.verify_claims(
        [("one", msg, pk.to_bytes(), sig.to_bytes())]
    )
    assert out == [True]
    assert host.dispatched_batches == []  # CPU path took it
    service.close()


@async_test
async def test_device_service_adapts_to_slow_device():
    """A device dispatch that measures slower than the CPU estimate
    makes later small batches route to the CPU (the slow-dispatch
    fallback), with periodic probes keeping recovery possible."""
    import hotstuff_tpu.crypto.async_service as asv

    msg = b"s" * 32
    pk, sig = _signed(31, msg)
    host = _FakeDeviceHost(kind="adapt-test", delay=0.05)  # 50 ms dispatch latency
    service = AsyncVerifyService.for_backend(host)
    claim = ("one", msg, pk.to_bytes(), sig.to_bytes())
    # first dispatch probes the device optimistically and measures 50 ms
    await service.verify_claims([claim])
    assert host.dispatched_batches == [1]
    assert service._device_ewma_s > 0.04
    # ~1 sig -> CPU estimate ~130 us << 50 ms: next ones go to CPU
    service._last_probe = asv.time.monotonic()  # suppress the probe window
    await service.verify_claims([claim])
    await service.verify_claims([claim])
    assert host.dispatched_batches == [1]
    # a huge batch's CPU estimate exceeds the EWMA -> device again
    # (distinct claims — identical ones would dedup to a single check;
    # 1500 sigs x CPU_BATCH_US_PER_SIG 45 us = 67.5 ms > the 50 ms EWMA)
    big = [
        ("one", bytes([i % 256, i // 256]) + b"\x00" * 30,
         pk.to_bytes(), sig.to_bytes())
        for i in range(1500)
    ]
    out = await service.verify_claims(big)
    assert len(out) == 1500
    assert host.dispatched_batches == [1, 1500]
    service.close()


def test_empty_shared_claim_is_false():
    """A certificate with zero signatures proves nothing: vacuous truth
    over an empty span would verify a votes=[] forgery."""
    out = eval_claims_sync(CpuVerifier(), [("shared", b"\x01" * 32, ())])
    assert out == [False]

    class Agg(CpuVerifier):
        prefers_aggregate = True

    out = eval_claims_sync(Agg(), [("shared", b"\x01" * 32, ())])
    assert out == [False]


@async_test
async def test_subquorum_qc_never_memoized_via_preverify(tmp_path):
    """SAFETY (r4 review): a sub-quorum QC with one valid self-signature
    must not enter the verified-QC cache through the burst preverifier —
    the cache hit would skip QC.verify's quorum-weight check forever."""
    from hotstuff_tpu.consensus import QC
    from hotstuff_tpu.consensus.messages import Vote
    from hotstuff_tpu.consensus.wire import TAG_TIMEOUT

    from .common import chain, fresh_base_port, keys, signed_timeout
    from .test_core import make_core, teardown

    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=60_000)
    try:
        ks = keys()
        block = chain(1)[0]
        # a forged "QC": ONE valid vote signature, far below 2f+1
        attacker_pk, attacker_sk = ks[3]
        vote = Vote(hash=block.digest(), round=1, author=attacker_pk)
        vote.signature = Signature.new(vote.digest(), attacker_sk)
        forged = QC(hash=block.digest(), round=1, votes=[(attacker_pk, vote.signature)])
        evil_timeout = signed_timeout(forged, 2, ks[3][0], ks[3][1])

        pre = await h.core._preverify_burst([(TAG_TIMEOUT, evil_timeout)])
        # the message may have its AUTHOR sig preverified or not, but the
        # forged certificate must NOT be in the verified cache
        assert forged._cache_key() not in h.core._verified_qcs
        # and the full handler path rejects it
        from hotstuff_tpu.consensus.errors import ConsensusError

        try:
            await h.core._handle_timeout(
                evil_timeout, sig_verified=0 in pre
            )
            raise AssertionError("sub-quorum high_qc accepted")
        except ConsensusError:
            pass
        assert forged._cache_key() not in h.core._verified_qcs
        # a votes=[] forgery is equally rejected
        empty = QC(hash=block.digest(), round=1, votes=[])
        t2 = signed_timeout(empty, 2, ks[2][0], ks[2][1])
        await h.core._preverify_burst([(TAG_TIMEOUT, t2)])
        assert empty._cache_key() not in h.core._verified_qcs
    finally:
        teardown(h)


@async_test
async def test_identical_claims_deduplicate_across_submissions():
    """One broadcast message's claims arrive from every co-located core
    in the same wave — the service verifies each unique claim once
    (verdicts are pure functions of the claim bytes)."""
    msg = b"d" * 32
    pk, sig = _signed(50, msg)
    host = _FakeDeviceHost(kind="dedup-test")
    service = AsyncVerifyService.for_backend(host)
    claim = ("one", msg, pk.to_bytes(), sig.to_bytes())

    outs = await asyncio.gather(
        *(service.verify_claims([claim]) for _ in range(8))
    )
    assert all(o == [True] for o in outs)
    assert host.dispatched_batches == [1]  # 8 submissions, ONE evaluation
    service.close()


@async_test
async def test_stalled_device_dispatch_does_not_stall_later_waves():
    """A stalled device dispatch must not queue later waves
    behind it: the deadline serves the stalled batch from the CPU, and
    while the device is busy new batches route to the CPU directly
    (measured failure mode: one stall collapsed a 32-node committee to
    a third of the CPU rate)."""
    import time as _time

    msg = b"t" * 32
    pk, sig = _signed(40, msg)
    host = _FakeDeviceHost(kind="stall-test", delay=0.5)  # 500 ms stall
    service = AsyncVerifyService.for_backend(host)
    claim = ("one", msg, pk.to_bytes(), sig.to_bytes())
    t0 = _time.perf_counter()
    out = await service.verify_claims([claim])
    first_wall = _time.perf_counter() - t0
    assert out == [True]
    # the deadline (100 ms floor, 4x EWMA) cut the wait well below the
    # 500 ms stall and the batch was served from the CPU
    assert first_wall < 0.45
    assert service.deadline_misses == 1
    # while the stalled dispatch is still in flight, new waves go
    # straight to the CPU (device busy)
    t0 = _time.perf_counter()
    out = await service.verify_claims([claim])
    assert out == [True]
    assert _time.perf_counter() - t0 < 0.2
    assert host.dispatched_batches == [1]  # no second device dispatch
    await asyncio.sleep(0.6)  # let the stalled dispatch land
    assert not service._device_busy
    service.close()


@async_test
async def test_qcmaker_skips_batch_when_all_preverified():
    """A cell whose every vote arrived pre-verified emits the QC with no
    quorum-time batch dispatch (the signatures are already proven)."""
    from hotstuff_tpu.consensus.aggregator import Aggregator
    from hotstuff_tpu.consensus.messages import Vote

    from .common import committee, fresh_base_port, keys

    class Counting(CpuVerifier):
        shared = 0

        def verify_shared_msg(self, d, votes):
            Counting.shared += 1
            return super().verify_shared_msg(d, votes)

    com = committee(fresh_base_port())
    ks = keys()
    agg = Aggregator(com, Counting(), self_key=ks[0][0])
    block_hash = Digest(b"\x09" * 32)
    qc = None
    for pk, sk in ks[:3]:
        vote = Vote(hash=block_hash, round=1, author=pk)
        vote.signature = Signature.new(vote.digest(), sk)
        qc = agg.add_vote(vote, 1, sig_verified=True) or qc
    assert qc is not None and qc.round == 1
    assert Counting.shared == 0  # no quorum batch needed

    # mixed cell: one unverified entry forces the quorum batch
    Counting.shared = 0
    agg2 = Aggregator(com, Counting(), self_key=ks[0][0])
    for i, (pk, sk) in enumerate(ks[:3]):
        vote = Vote(hash=block_hash, round=2, author=pk)
        vote.signature = Signature.new(vote.digest(), sk)
        agg2.add_vote(vote, 2, sig_verified=i != 1)
    assert Counting.shared == 1


@async_test
async def test_preverified_proposal_skips_sync_crypto(tmp_path):
    """A proposal whose claims all pass arrives at the handler with
    sigs_verified=True: zero synchronous signature work on the loop."""
    from hotstuff_tpu.consensus.wire import TAG_PROPOSE

    from .common import chain, fresh_base_port
    from .test_core import make_core, teardown

    class Counting(CpuVerifier):
        ones = 0
        shared = 0

        def verify_one(self, d, pk, sig):
            Counting.ones += 1
            return super().verify_one(d, pk, sig)

        def verify_shared_msg(self, d, votes):
            Counting.shared += 1
            return super().verify_shared_msg(d, votes)

    h = make_core(tmp_path, fresh_base_port(), 0, timeout_ms=60_000)
    try:
        blocks = chain(2)
        burst = [(TAG_PROPOSE, blocks[1])]
        pre = await h.core._preverify_burst(burst)
        assert pre == {0}
        # now swap in the counting verifier: the handler must not touch it
        h.core.verifier = Counting()
        h.core.aggregator.verifier = h.core.verifier
        await h.core._dispatch(burst[0], sig_verified=True)
        assert Counting.ones == 0
        assert Counting.shared == 0
        # and the embedded QC is memoized for future bursts
        assert blocks[1].qc._cache_key() in h.core._verified_qcs
    finally:
        teardown(h)


def test_registry_prunes_closed_loops():
    """Advisor r4: the per-(loop, kind) registry must not pin closed
    loops (and their idle executors) forever — stale entries are pruned
    on the next for_backend lookup."""

    class DeviceBackend(CpuVerifier):
        async_kind = "test-kind"
        device_ready = False

    backend = DeviceBackend()

    async def acquire():
        return AsyncVerifyService.for_backend(backend)

    loop1 = asyncio.new_event_loop()
    svc1 = loop1.run_until_complete(acquire())
    loop1.close()
    assert any(s is svc1 for _, s in AsyncVerifyService._registry.values())

    loop2 = asyncio.new_event_loop()
    svc2 = loop2.run_until_complete(acquire())
    try:
        # the closed loop's entry is gone; only the live one remains
        assert not any(
            s is svc1 for _, s in AsyncVerifyService._registry.values()
        )
        assert any(
            s is svc2 for _, s in AsyncVerifyService._registry.values()
        )
    finally:
        svc2.close()
        loop2.close()


class _GatedDeviceHost:
    """Device host whose every dispatch BLOCKS until its per-wave gate
    is released — drives out-of-order completion, per-wave failure
    injection, and in-flight concurrency tracking for the pipeline
    tests."""

    def __init__(self, kind):
        import threading

        self.async_kind = kind
        self.device_ready = True
        self.cpu_backend = CpuVerifier()
        # gates held open mid-test must not trip the dispatch deadline
        self.dispatch_deadline_s = 5.0
        self.gates: list = []
        self.fail_waves: set = set()
        self.concurrent = 0
        self.max_concurrent = 0
        self._lock = threading.Lock()
        host = self

        class _Dispatch:
            def verify_many(self, digests, pks, sigs, aggregate_ok=False):
                import threading as _threading

                with host._lock:
                    idx = len(host.gates)
                    gate = _threading.Event()
                    host.gates.append(gate)
                    host.concurrent += 1
                    host.max_concurrent = max(
                        host.max_concurrent, host.concurrent
                    )
                try:
                    assert gate.wait(5.0), "test gate never released"
                    if idx in host.fail_waves:
                        raise RuntimeError(f"wave {idx} failed")
                    return CpuVerifier().verify_many(digests, pks, sigs)
                finally:
                    with host._lock:
                        host.concurrent -= 1

        self.async_backend = _Dispatch()


async def _until(cond, timeout=2.0):
    import time as _time

    t0 = _time.perf_counter()
    while not cond():
        assert _time.perf_counter() - t0 < timeout, "condition not reached"
        await asyncio.sleep(0.005)


@async_test
async def test_out_of_order_completion_resolves_right_futures():
    """Two waves in flight at depth 2: the LATER wave lands first and
    resolves its own waiters with its own verdicts while the earlier
    wave is still on the device (async readback, ISSUE 5)."""
    msg_a, msg_b = b"a" * 32, b"b" * 32
    pk, sig_a = _signed(60, msg_a)
    claim_a = ("one", msg_a, pk.to_bytes(), sig_a.to_bytes())
    # sig_a over msg_b is INVALID — distinct verdicts prove the futures
    # were matched to the right waves
    claim_b = ("one", msg_b, pk.to_bytes(), sig_a.to_bytes())
    host = _GatedDeviceHost("ooo-test")
    service = AsyncVerifyService(host, device=True, pipeline_depth=2)
    task_a = asyncio.ensure_future(service.verify_claims([claim_a]))
    await _until(lambda: len(host.gates) == 1)
    task_b = asyncio.ensure_future(service.verify_claims([claim_b]))
    await _until(lambda: len(host.gates) == 2)
    assert service.peak_inflight == 2
    host.gates[1].set()  # wave B lands FIRST
    assert (await task_b) == [False]
    assert not task_a.done()  # A still parked on the device
    host.gates[0].set()
    assert (await task_a) == [True]
    service.close()


@async_test
async def test_failed_wave_poisons_only_its_own_futures():
    """A backend exception on wave N reaches wave N's waiters and ONLY
    wave N's — the in-flight wave behind it lands normally."""
    msg_a, msg_b = b"c" * 32, b"e" * 32
    pk_a, sig_a = _signed(61, msg_a)
    pk_b, sig_b = _signed(62, msg_b)
    host = _GatedDeviceHost("poison-test")
    host.fail_waves = {0}
    service = AsyncVerifyService(host, device=True, pipeline_depth=2)
    task_a = asyncio.ensure_future(
        service.verify_claims([("one", msg_a, pk_a.to_bytes(), sig_a.to_bytes())])
    )
    await _until(lambda: len(host.gates) == 1)
    task_b = asyncio.ensure_future(
        service.verify_claims([("one", msg_b, pk_b.to_bytes(), sig_b.to_bytes())])
    )
    await _until(lambda: len(host.gates) == 2)
    host.gates[0].set()
    try:
        await task_a
        raise AssertionError("poisoned wave returned a verdict")
    except RuntimeError:
        pass
    host.gates[1].set()
    assert (await task_b) == [True]
    service.close()


@async_test
async def test_depth_cap_backpressure_queues_next_wave(monkeypatch):
    """Wave K+1 QUEUES for a pipeline slot at full occupancy instead of
    dispatching past the depth cap (or spilling to the CPU when the
    device is the forced route), and dispatches as soon as a wave
    lands."""
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    claims = []
    for i in range(3):
        msg = bytes([100 + i]) * 32
        pk, sig = _signed(70 + i, msg)
        claims.append(("one", msg, pk.to_bytes(), sig.to_bytes()))
    host = _GatedDeviceHost("cap-test")
    service = AsyncVerifyService(host, device=True, pipeline_depth=2)
    tasks = []
    for i in range(2):
        tasks.append(asyncio.ensure_future(service.verify_claims([claims[i]])))
        await _until(lambda i=i: len(host.gates) == i + 1)
    tasks.append(asyncio.ensure_future(service.verify_claims([claims[2]])))
    await asyncio.sleep(0.05)
    # the third wave queued: never a third concurrent dispatch
    assert len(host.gates) == 2
    assert service.pipeline_waits == 1
    host.gates[0].set()  # a slot frees -> the queued wave dispatches
    await _until(lambda: len(host.gates) == 3)
    host.gates[1].set()
    host.gates[2].set()
    assert await asyncio.gather(*tasks) == [[True]] * 3
    assert host.max_concurrent <= 2
    assert service.peak_inflight == 2
    service.close()


@async_test
async def test_depth_one_preserves_single_inflight(monkeypatch):
    """pipeline_depth=1 restores the old single-in-flight dispatch gate:
    at no point are two device dispatches concurrent."""
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    msg_a, msg_b = b"f" * 32, b"g" * 32
    pk_a, sig_a = _signed(80, msg_a)
    pk_b, sig_b = _signed(81, msg_b)
    host = _GatedDeviceHost("depth1-test")
    service = AsyncVerifyService(host, device=True, pipeline_depth=1)
    task_a = asyncio.ensure_future(
        service.verify_claims([("one", msg_a, pk_a.to_bytes(), sig_a.to_bytes())])
    )
    await _until(lambda: len(host.gates) == 1)
    task_b = asyncio.ensure_future(
        service.verify_claims([("one", msg_b, pk_b.to_bytes(), sig_b.to_bytes())])
    )
    await asyncio.sleep(0.05)
    assert len(host.gates) == 1  # second wave queued behind the gate
    host.gates[0].set()
    await _until(lambda: len(host.gates) == 2)
    host.gates[1].set()
    assert await asyncio.gather(task_a, task_b) == [[True], [True]]
    assert host.max_concurrent == 1
    assert service.peak_inflight == 1
    service.close()


def test_route_under_full_occupancy(monkeypatch):
    """Routing at the depth cap: device-preferred waves queue ("wait"),
    device-losing waves spill to the CPU, a due probe NEVER fires (it
    would need the slot we don't have), and an overdue in-flight wave
    routes everything to the CPU."""
    import time as _time

    class DeviceBackend(CpuVerifier):
        async_kind = "occupancy-route-test"
        device_ready = True

    monkeypatch.delenv("HOTSTUFF_FORCE_DEVICE_ROUTE", raising=False)
    service = AsyncVerifyService(DeviceBackend(), device=True, pipeline_depth=2)
    now = _time.monotonic()
    service._inflight = {1: now + 10.0, 2: now + 10.0}
    service._last_probe = 0.0  # a probe is long overdue
    # device EWMA wins for this batch size -> queue for a slot
    service._device_ewma_s = 0.001
    assert service._route_device(256) == "wait"
    # device EWMA loses badly -> CPU, and the due probe must NOT fire
    service._device_ewma_s = 10.0
    assert service._route_device(1) == "cpu"
    # the forced route queues rather than spilling
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    assert service._route_device(1) == "wait"
    monkeypatch.delenv("HOTSTUFF_FORCE_DEVICE_ROUTE")
    # an OVERDUE in-flight wave routes everything to the CPU, once it
    # has had its one yield of the loop to be delivered in
    service._inflight[1] = now - 1.0
    service._device_ewma_s = 0.001
    assert service._route_device(256) == "wait"
    assert service._route_device(256) == "cpu"
    assert service._route_device(256) == "cpu"
    service._inflight.clear()
    # below the cap the due probe finally fires on a losing EWMA
    service._device_ewma_s = 10.0
    assert service._route_device(1) == "probe"
    service.close()


@async_test
@pytest.mark.parametrize("lands", [True, False], ids=["lands", "stalled"])
async def test_overdue_wave_is_given_one_yield_before_the_cpu(
    lands, monkeypatch
):
    """A wave whose deadline stamp has passed while the process stood
    still (a pause of the host, a long pass of the loop) has most often
    landed, its delivery queued behind the dispatcher: the next wave
    waits 5 ms for that delivery and then takes the device.  A wave
    that is really stuck sends the traffic round it as before."""
    msg = b"g" * 32
    pk, sig = _signed(61, msg)
    claim = ("one", msg, pk.to_bytes(), sig.to_bytes())
    # the cells' pin: the gate's wait must not read as a slow device
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    host = _GatedDeviceHost(f"overdue-grace-{lands}")
    service = AsyncVerifyService.for_backend(host)
    first = asyncio.ensure_future(service.verify_claims([claim]))
    await _until(lambda: len(host.gates) == 1)
    # the clock ran past the first wave's stamp while nothing else did
    (serial,) = service._inflight
    service._inflight[serial] -= 10.0
    if lands:
        # the first wave lands inside the second's yield (the test
        # waits for the delivery itself, not for 5 ms of a loaded host)
        park = service._wait_for_slot

        async def land_then_park():
            host.gates[0].set()
            await _until(lambda: serial not in service._inflight)
            await park()

        service._wait_for_slot = land_then_park
    second = asyncio.ensure_future(service.verify_claims([claim]))
    if lands:
        await _until(lambda: len(host.gates) == 2)
        host.gates[1].set()
    assert await second == [True]
    host.gates[0].set()
    assert await first == [True]
    assert service.pipeline_waits == 1
    assert service.cpu_dispatches == (0 if lands else 1)
    assert service.device_dispatches == (2 if lands else 1)
    assert not service._graced
    service.close()


def test_pipeline_depth_from_env(monkeypatch):
    from hotstuff_tpu.crypto.async_service import (
        DEFAULT_PIPELINE_DEPTH,
        pipeline_depth_from_env,
    )

    monkeypatch.delenv("HOTSTUFF_VERIFY_PIPELINE", raising=False)
    assert pipeline_depth_from_env() == DEFAULT_PIPELINE_DEPTH
    monkeypatch.setenv("HOTSTUFF_VERIFY_PIPELINE", "4")
    assert pipeline_depth_from_env() == 4
    monkeypatch.setenv("HOTSTUFF_VERIFY_PIPELINE", "0")
    assert pipeline_depth_from_env() == 1  # floor: depth 0 is depth 1


def test_wave_buckets_from_env(monkeypatch):
    from hotstuff_tpu.crypto.async_service import (
        DEFAULT_WAVE_BUCKETS,
        wave_buckets_from_env,
    )

    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    assert wave_buckets_from_env() == DEFAULT_WAVE_BUCKETS
    monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "64,16,256")
    assert wave_buckets_from_env() == (16, 64, 256)  # sorted, deduped
    monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "off")
    assert wave_buckets_from_env() == ()
    monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "0")
    assert wave_buckets_from_env() == ()
    monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "bogus")
    assert wave_buckets_from_env() == DEFAULT_WAVE_BUCKETS


def test_pad_claim_is_a_valid_signature():
    """The fixed-shape filler claim must be VALID: an invalid pad would
    poison the CPU batch equation fallback for an otherwise all-valid
    packed wave (eval_claims_sync's flat fast path is all-or-nothing)."""
    service = AsyncVerifyService(CpuVerifier())
    pad = service._pad_claim_tuple()
    assert pad[0] == "one"
    assert eval_claims_sync(CpuVerifier(), [pad]) == [True]


@async_test
async def test_fixed_shape_padding_hits_bucket_and_preserves_verdicts(
    monkeypatch,
):
    """A device-routed wave on a padding-capable backend is padded to
    the smallest bucket (ISSUE 6) — and the pads can never flip a real
    claim's verdict, including an INVALID real claim's."""
    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    host = _FakeDeviceHost(kind="pack-test")
    host.supports_wave_padding = True
    service = AsyncVerifyService(host, device=True)
    claims = []
    for i in range(4):
        m = bytes([120 + i]) * 32
        pk, s = _signed(100 + i, m)
        claims.append(("one", m, pk.to_bytes(), s.to_bytes()))
    # claims[0]'s signature over a different digest is INVALID
    bad = ("one", b"k" * 32, claims[0][2], claims[0][3])
    out = await service.verify_claims(claims + [bad])
    assert out == [True] * 4 + [False]
    # 5 real sigs padded to the 16-bucket: the device saw EXACTLY 16
    assert host.dispatched_batches == [16]
    assert service.packed_waves == 1
    assert service.pad_sigs == 11
    # an exact-fit wave passes through unpadded
    fit = []
    for i in range(16):
        m = bytes([10, i]) + b"\x00" * 30
        pk, s = _signed(130, m)
        fit.append(("one", m, pk.to_bytes(), s.to_bytes()))
    out = await service.verify_claims(fit)
    assert len(out) == 16
    assert host.dispatched_batches[-1] == 16
    assert service.packed_waves == 1  # no pads added for the exact fit
    service.close()


@async_test
async def test_padding_needs_backend_opt_in(monkeypatch):
    """Backends that do NOT advertise supports_wave_padding see exactly
    the submitted claims (synthetic hosts, CPU fallback, aggregate
    backends) — no silent filler rides their dispatches."""
    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    msg = b"l" * 32
    pk, sig = _signed(105, msg)
    host = _FakeDeviceHost(kind="no-pack-test")  # no opt-in attribute
    service = AsyncVerifyService(host, device=True)
    out = await service.verify_claims(
        [("one", msg, pk.to_bytes(), sig.to_bytes())]
    )
    assert out == [True]
    assert host.dispatched_batches == [1]
    assert service.packed_waves == 0 and service.pad_sigs == 0
    service.close()


def test_warm_buckets_drives_every_bucket_shape(monkeypatch):
    """warm_buckets() pre-compiles each configured bucket size through
    the forced-device dispatch view, so the first real wave of any
    bucket never pays a cold compile mid-consensus."""
    monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "4,8")
    host = _FakeDeviceHost(kind="warm-test")
    host.supports_wave_padding = True
    service = AsyncVerifyService(host, device=True)
    service.warm_buckets()
    assert host.dispatched_batches == [4, 8]
    # non-padding backends and inline services are no-ops
    plain = AsyncVerifyService(CpuVerifier())
    plain.warm_buckets()
    service.close()
    plain.close()


@async_test
async def test_round_window_coalesces_qc_and_tc_into_one_wave(monkeypatch):
    """HOTSTUFF_COALESCE_WINDOW_MS holds the wave open so the QC and TC
    claims of one round merge into ONE device dispatch, with the claim
    table fanning each submitter its own verdicts on readback."""
    monkeypatch.setenv("HOTSTUFF_COALESCE_WINDOW_MS", "80")
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    msg = b"i" * 32
    qc_pairs = [_signed(91 + i, msg) for i in range(4)]
    qc_claim = (
        "shared",
        msg,
        tuple((pk.to_bytes(), s.to_bytes()) for pk, s in qc_pairs),
    )
    tc_claims = []
    for i in range(3):
        m = bytes([110 + i]) * 32
        pk, s = _signed(95 + i, m)
        tc_claims.append(("one", m, pk.to_bytes(), s.to_bytes()))
    # one INVALID TC entry proves the merged wave's per-claim fanout
    bad = ("one", b"j" * 32, tc_claims[0][2], tc_claims[0][3])
    host = _FakeDeviceHost(kind="window-test")
    service = AsyncVerifyService(host, device=True)
    assert abs(service.coalesce_window_s - 0.08) < 1e-9
    qc_fut = asyncio.ensure_future(service.verify_claims([qc_claim]))
    await asyncio.sleep(0.02)  # well inside the window
    tc_fut = asyncio.ensure_future(
        service.verify_claims(tc_claims + [bad])
    )
    assert (await qc_fut) == [True]
    assert (await tc_fut) == [True, True, True, False]
    # 4 QC sigs + 4 TC sigs were dispatched ONCE
    assert host.dispatched_batches == [8]
    assert service.device_dispatches == 1
    service.close()


@async_test
async def test_dispatch_loop_shuts_down_on_close():
    """Service close stops the dedicated dispatch loop's slot threads
    (and deregisters it from the atexit shutdown set) — no leaked
    thread outlives its service."""
    import hotstuff_tpu.crypto.async_service as asv

    msg = b"h" * 32
    pk, sig = _signed(90, msg)
    host = _FakeDeviceHost(kind="lifecycle-test")
    service = AsyncVerifyService.for_backend(host)
    out = await service.verify_claims(
        [("one", msg, pk.to_bytes(), sig.to_bytes())]
    )
    assert out == [True]
    dl = service._dispatch
    assert dl is not None and dl in asv._live_dispatch_loops
    threads = list(dl._threads)
    assert threads and all(t.is_alive() for t in threads)
    assert all(t.name.startswith("verify-slot-") for t in threads)
    assert len(threads) == service.pipeline_depth
    service.close()
    assert service._dispatch is None
    assert dl not in asv._live_dispatch_loops
    for t in threads:
        t.join(timeout=2.0)
    assert not any(t.is_alive() for t in threads)
    # a closed loop refuses new work instead of silently dropping it
    try:
        dl.submit(lambda: None, lambda r, e: None)
        raise AssertionError("closed dispatch loop accepted a submit")
    except RuntimeError:
        pass
