"""View-change-storm micro-benchmark (BASELINE config 4).

The storm shape: a committee of N = 256 (f = 85) hits a round timeout.
Every correct node then has to process, on its consensus loop:

1. a **timeout flood** — 2f+1 = 171 incoming ``Timeout`` messages, each
   carrying the sender's single signature AND the same 171-vote
   ``high_qc`` (the most expensive repeated check in the protocol;
   the per-core verified-QC memo collapses the n identical embedded-QC
   verifications to one — measured here with and without the memo);
2. **TC verification**, two shapes — the REALISTIC certificate (every
   entry shares one timeout digest, so same-digest grouped aggregation
   applies) and the adversarial worst case (171 DISTINCT digests — the
   full ``verify_many`` multi-pairing; the reference verifies these
   sequentially, consensus/src/messages.rs:305-311).

Backends measured: ed25519-cpu (OpenSSL), ed25519-tpu (the batch
kernel, optional — pass ``--device``), and bls-cpu (aggregate QC =
one pairing equality regardless of committee size; TC = one
random-weight multi-pairing).

Writes a human-readable report and appends to
``results/storm-<N>-<quorum>-<backend>.txt``.
"""

from __future__ import annotations

import time

N_DEFAULT = 256


def _fmt_ms(s: float) -> str:
    return f"{s * 1e3:.1f} ms"


def _ed25519_fixture(n: int, quorum: int):
    """(committee, timeouts, (tc_realistic, tc_worst), high_qc)."""
    from hotstuff_tpu.consensus import QC, TC, Timeout, Vote
    from hotstuff_tpu.consensus.config import Committee
    from hotstuff_tpu.crypto import Digest, Signature, generate_keypair
    from hotstuff_tpu.crypto.signature import Signature as Sig

    seed = b"\x51" * 32
    members = [generate_keypair(seed, i) for i in range(n)]
    committee = Committee.new(
        [(pk, 1, ("127.0.0.1", 40_000 + i)) for i, (pk, _) in enumerate(members)]
    )
    # the storm's shared high_qc: a full-quorum QC for round 9
    block_digest = Digest.of(b"storm high-qc block")
    vote_digest = Vote(hash=block_digest, round=9, author=members[0][0]).digest()
    high_qc = QC(
        hash=block_digest,
        round=9,
        votes=[
            (pk, Sig.new(vote_digest, sk)) for pk, sk in members[:quorum]
        ],
    )
    timeouts = []
    for pk, sk in members[:quorum]:
        t = Timeout(high_qc=high_qc, round=10, author=pk)
        t.signature = Signature.new(t.digest(), sk)
        timeouts.append(t)
    from hotstuff_tpu.consensus.messages import timeout_digest

    # the REALISTIC TC formed from the flood above: every entry carries
    # high_qc_round = 9, so all entries sign the SAME timeout digest
    tc = TC(
        round=10,
        votes=[
            (pk, Signature.new(timeout_digest(10, 9), sk), 9)
            for pk, sk in members[:quorum]
        ],
    )
    # adversarial worst case: DISTINCT per-entry digests (each entry
    # claims its own high_qc_round) — defeats same-digest grouping
    tc_worst = TC(
        round=10,
        votes=[
            (pk, Signature.new(timeout_digest(10, i), sk), i)
            for i, (pk, sk) in enumerate(members[:quorum])
        ],
    )
    return committee, timeouts, (tc, tc_worst), high_qc


def _bls_fixture(n: int, quorum: int):
    from hotstuff_tpu.consensus import QC, TC, Timeout, Vote
    from hotstuff_tpu.consensus.config import Committee
    from hotstuff_tpu.crypto import Digest, Signature
    from hotstuff_tpu.crypto.bls.service import BlsSigningService
    from hotstuff_tpu.crypto.scheme import bls_keygen, bls_pop

    seed = b"\x52" * 32
    members = [bls_keygen(seed, i) for i in range(n)]
    committee = Committee.new(
        [(pk, 1, ("127.0.0.1", 41_000 + i)) for i, (pk, _) in enumerate(members)],
        scheme="bls",
        pops={pk: bls_pop(secret) for pk, secret in members},
    )
    signers = [BlsSigningService(secret) for _, secret in members[:quorum]]
    block_digest = Digest.of(b"storm high-qc block")
    vote_digest = Vote(hash=block_digest, round=9, author=members[0][0]).digest()
    high_qc = QC(
        hash=block_digest,
        round=9,
        votes=[
            (members[i][0], signers[i].sign_sync(vote_digest))
            for i in range(quorum)
        ],
    )
    timeouts = []
    for i in range(quorum):
        t = Timeout(high_qc=high_qc, round=10, author=members[i][0])
        t.signature = signers[i].sign_sync(t.digest())
        timeouts.append(t)
    from hotstuff_tpu.consensus.messages import timeout_digest

    # realistic TC (every entry shares high_qc_round = 9 — same digest)
    tc = TC(
        round=10,
        votes=[
            (members[i][0], signers[i].sign_sync(timeout_digest(10, 9)), 9)
            for i in range(quorum)
        ],
    )
    # adversarial worst case: distinct per-entry digests
    tc_worst = TC(
        round=10,
        votes=[
            (members[i][0], signers[i].sign_sync(timeout_digest(10, i)), i)
            for i in range(quorum)
        ],
    )
    return committee, timeouts, (tc, tc_worst), high_qc


def _measure(committee, timeouts, tc, verifier) -> dict[str, float]:
    out: dict[str, float] = {}
    if hasattr(verifier, "precompute"):
        # epoch setup, exactly like node boot (node/node.py): committee
        # key decode/caching is not storm work
        verifier.precompute([pk.to_bytes() for pk in committee.authorities])
    # 1a. timeout flood WITH the per-core verified-QC memo (product path)
    cache: set = set()
    t0 = time.perf_counter()
    for t in timeouts:
        t.verify(committee, verifier, qc_cache=cache)
    out["flood_memo_s"] = time.perf_counter() - t0
    # 1b. naive flood: every timeout re-verifies the embedded high_qc
    t0 = time.perf_counter()
    for t in timeouts[: max(4, len(timeouts) // 16)]:  # sampled — O(n) QCs
        t.verify(committee, verifier, qc_cache=None)
    sampled = max(4, len(timeouts) // 16)
    out["flood_naive_s"] = (time.perf_counter() - t0) / sampled * len(timeouts)
    # 1c. the burst path (Core._preverify_timeout_burst): per 64-message
    # burst ONE aggregate signature check over the shared timeout
    # digest, then per-timeout stake + memoized-QC checks only
    cache2: set = set()
    t0 = time.perf_counter()
    for start in range(0, len(timeouts), 64):
        chunk = timeouts[start : start + 64]
        ok = verifier.verify_shared_msg(
            chunk[0].digest(), [(t.author, t.signature) for t in chunk]
        )
        assert ok
        for t in chunk:
            t.verify(committee, verifier, qc_cache=cache2, sig_verified=True)
    out["flood_burst_s"] = time.perf_counter() - t0
    # 2. TC verification: realistic (all entries share one timeout
    # digest — same-digest grouping applies) and adversarial worst case
    # (every digest distinct — full multi-pairing)
    tc_real, tc_worst = tc
    t0 = time.perf_counter()
    tc_real.verify(committee, verifier)
    out["tc_verify_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    tc_worst.verify(committee, verifier)
    out["tc_worst_verify_s"] = time.perf_counter() - t0
    # 3. the shared high_qc alone (the QC shape at committee scale)
    t0 = time.perf_counter()
    timeouts[0].high_qc.verify(committee, verifier)
    out["qc_verify_s"] = time.perf_counter() - t0
    return out


def _measure_offloop_tc(committee, tc_worst, verifier) -> dict[str, float]:
    """The adversarial TC through the PRODUCTION async claims path
    (VERDICT r3 item 8): the worker-thread offload runs the n+1-Miller-
    loop multi-pairing off the event loop (ctypes releases the GIL), so
    the loop keeps serving timers/messages while the verdict computes.
    Reports the verify wall time AND the worst event-loop stall observed
    by a 5 ms heartbeat during it — the stall, not the wall, is what a
    view change feels."""
    import asyncio

    from hotstuff_tpu.crypto.async_service import AsyncVerifyService

    out: dict[str, float] = {}

    async def run() -> None:
        service = AsyncVerifyService.for_backend(verifier)
        lags: list[float] = []
        stop = asyncio.Event()

        async def heartbeat():
            loop = asyncio.get_running_loop()
            while not stop.is_set():
                t0 = loop.time()
                await asyncio.sleep(0.005)
                lags.append(loop.time() - t0 - 0.005)

        hb = asyncio.ensure_future(heartbeat())
        await asyncio.sleep(0.05)  # heartbeat baseline
        t0 = time.perf_counter()
        verdicts = await service.verify_claims(tc_worst.claims())
        out["offloop_tc_worst_s"] = time.perf_counter() - t0
        assert all(verdicts)
        stop.set()
        await hb
        out["offloop_max_stall_s"] = max(lags) if lags else 0.0
        service.close()

    asyncio.run(run())
    return out


def run_storm(
    nodes: int = N_DEFAULT, device: bool = False, bls: bool = True
) -> dict[str, dict[str, float]]:
    from hotstuff_tpu.crypto.service import CpuVerifier

    quorum = 2 * nodes // 3 + 1
    results: dict[str, dict[str, float]] = {}

    committee, timeouts, tc, _ = _ed25519_fixture(nodes, quorum)
    results["ed25519-cpu"] = _measure(committee, timeouts, tc, CpuVerifier())

    if device:
        from hotstuff_tpu.tpu.ed25519 import BatchVerifier

        # production hybrid routing (node/node.py): single-signature
        # verifies stay on CPU, certificate-sized batches go to the
        # device — forcing min_device_batch=0 here would time the
        # dispatch fixed cost 171x on the flood path, which no node pays
        v = BatchVerifier()
        v.precompute([pk.to_bytes() for pk in committee.authorities])
        v.warmup(batch=quorum)
        results["ed25519-tpu"] = _measure(committee, timeouts, tc, v)

    if bls:
        from hotstuff_tpu.crypto.scheme import make_cpu_verifier

        committee, timeouts, tc, _ = _bls_fixture(nodes, quorum)
        bls_verifier = make_cpu_verifier("bls")
        results["bls-cpu"] = _measure(committee, timeouts, tc, bls_verifier)
        if getattr(bls_verifier, "async_kind", None):
            results["bls-cpu"].update(
                _measure_offloop_tc(committee, tc[1], bls_verifier)
            )
        if device:
            # the opt-in TPU ladder offload for the all-distinct storm
            # (VERDICT r5 item 8): measured honestly next to the host
            # route — on this rig it LOSES (per-op-overhead-bound VPU
            # shape, docs/ROUND5.md), which is why it is opt-in
            from hotstuff_tpu.crypto.scheme import make_device_verifier

            v = make_device_verifier("bls", "tpu")
            v.warmup_storm_offload(quorum)
            # only publish the row when the offload will actually serve
            # this quorum size — a declined offload (e.g. quorum < 16)
            # would silently measure the host route under the
            # offload label
            if v.storm_offload_engaged(quorum):
                results["bls-tpu-storm-offload"] = _measure(
                    committee, timeouts, tc, v
                )
            else:
                print(
                    f" storm offload declined for quorum={quorum} "
                    "(not warmed or below the n>=16 floor); "
                    "bls-tpu-storm-offload row skipped"
                )
    return results


def format_report(nodes: int, results: dict[str, dict[str, float]]) -> str:
    quorum = 2 * nodes // 3 + 1
    lines = [
        "-" * 64,
        " VIEW-CHANGE STORM (BASELINE config 4)",
        f" Committee: {nodes} nodes (f = {(nodes - 1) // 3}), quorum = {quorum}",
        "-" * 64,
    ]
    for backend, m in results.items():
        lines += [
            f" + {backend}:",
            f"   Timeout flood x{quorum} (verified-QC memo): "
            f"{_fmt_ms(m['flood_memo_s'])}",
            f"   Timeout flood x{quorum} (naive, extrapolated): "
            f"{_fmt_ms(m['flood_naive_s'])}",
            f"   Timeout flood x{quorum} (burst aggregate): "
            f"{_fmt_ms(m['flood_burst_s'])}",
            f"   TC verify ({quorum} entries, shared high_qc_round): "
            f"{_fmt_ms(m['tc_verify_s'])}",
            f"   TC verify ({quorum} DISTINCT digests, worst case): "
            f"{_fmt_ms(m['tc_worst_verify_s'])}",
            f"   QC verify ({quorum} votes, shared digest): "
            f"{_fmt_ms(m['qc_verify_s'])}",
        ]
        if "offloop_tc_worst_s" in m:
            lines += [
                f"   TC worst case OFF-LOOP (async claims path): "
                f"{_fmt_ms(m['offloop_tc_worst_s'])} wall, "
                f"max event-loop stall "
                f"{_fmt_ms(m['offloop_max_stall_s'])}",
            ]
    lines += [
        " NOTE: every device time above includes the dispatch latency.",
        "-" * 64,
    ]
    return "\n".join(lines)
