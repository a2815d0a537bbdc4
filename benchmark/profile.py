"""`python -m benchmark profile` — the verify-pipeline waterfall.

Drives QC-shaped claim waves through the SAME dispatch path production
uses (AsyncVerifyService + LazyDeviceVerifier), with the span profiler
(hotstuff_tpu/telemetry/spans.py) on, and renders where each wave's wall
time went stage by stage:

    claim arrival -> coalesce.wait -> route.decide -> stage.pack ->
    stage.slot_wait -> flatten -> prepare -> dispatch ->
    device.execute -> readback -> verdict.fanout

The SUMMARY shows per-stage p50/p99 plus each stage's share of the
externally measured end-to-end latency, and a coverage line — the sum of
leaf-stage p50s over the e2e p50.  Coverage >= ~90% means the waterfall
accounts for the 0.5 ms-device / 91 ms-rig gap (ISSUE 4 acceptance);
a low number means a stage is missing its instrumentation.

``--capture DIR`` additionally wraps the largest batch size's waves in
``jax.profiler.trace(DIR)`` so the device window can be inspected in
TensorBoard/Perfetto at XLA-op granularity.
"""

from __future__ import annotations

import os
import time

from hotstuff_tpu.telemetry import spans as _spans

#: waves driven per batch size before stats (plus WARMUP_WAVES discarded)
DEFAULT_WAVES = 20
WARMUP_WAVES = 3

#: sustained wave-train mode: waves per train / trains measured
DEFAULT_TRAIN_WAVES = 8
DEFAULT_TRAIN_REPS = 10


def _percentile(values: list[float], pct: float) -> float:
    """Nearest-rank percentile over the raw per-wave samples (no
    histogram bucketing — the waterfall's point is exactness)."""
    if not values:
        return 0.0
    ordered = sorted(values)
    k = max(0, min(len(ordered) - 1, round(pct / 100 * (len(ordered) - 1))))
    return ordered[k]


def make_qc_claim(n: int, scheme: str = "ed25519"):
    """One "shared" claim with n committee signatures over one digest —
    the QC verify shape.
    ``scheme="bls"`` builds the same claim over BLS12-381 material
    (96-byte G2 pubkeys, 48-byte G1 signatures)."""
    from hotstuff_tpu.crypto import Digest

    shared = Digest.of(b"profile block digest")
    votes = []
    pks = []
    if scheme == "bls":
        from hotstuff_tpu.crypto.bls import keygen as bls_keygen

        for i in range(n):
            pk, sk = bls_keygen(b"profile-bls" + i.to_bytes(4, "little"))
            pks.append(pk.to_bytes())
            votes.append(
                (pk.to_bytes(), sk.sign(shared.to_bytes()).to_bytes())
            )
    else:
        from hotstuff_tpu.crypto import Signature, generate_keypair

        for i in range(n):
            pk, sk = generate_keypair(b"\x33" * 32, i)
            pks.append(pk.to_bytes())
            votes.append(
                (pk.to_bytes(), Signature.new(shared, sk).to_bytes())
            )
    return ("shared", shared.to_bytes(), tuple(votes)), pks


def make_train_claims(n: int, waves: int, scheme: str = "ed25519"):
    """``waves`` distinct-digest QC claims over ONE committee.  Distinct
    digests defeat the service's cross-wave claim dedup (every wave is
    real work); a single committee keeps the device-resident key cache
    hot across the whole train."""
    from hotstuff_tpu.crypto import Digest

    if scheme == "bls":
        from hotstuff_tpu.crypto.bls import keygen as bls_keygen

        keys = [
            bls_keygen(b"train-bls" + i.to_bytes(4, "little"))
            for i in range(n)
        ]
        pks = [pk.to_bytes() for pk, _ in keys]
        claims = []
        for w in range(waves):
            d = Digest.of(b"train wave %d" % w)
            votes = tuple(
                (pk.to_bytes(), sk.sign(d.to_bytes()).to_bytes())
                for pk, sk in keys
            )
            claims.append(("shared", d.to_bytes(), votes))
        return claims, pks

    from hotstuff_tpu.crypto import Signature, generate_keypair

    keys = [generate_keypair(b"\x44" * 32, i) for i in range(n)]
    pks = [pk.to_bytes() for pk, _ in keys]
    claims = []
    for w in range(waves):
        d = Digest.of(b"train wave %d" % w)
        votes = tuple(
            (pk.to_bytes(), Signature.new(d, sk).to_bytes())
            for pk, sk in keys
        )
        claims.append(("shared", d.to_bytes(), votes))
    return claims, pks


def waterfall(span_rows: list[tuple], e2e_ms: list[float]) -> dict:
    """Aggregate drained recorder rows ``(name, t0_ns, dur_ns, depth,
    thread, ids)`` against the externally measured per-wave ``e2e_ms``.

    Returns {"e2e_ms": {p50, p99}, "stages": {name: {p50_ms, p99_ms,
    count, pct_of_e2e}}, "coverage_pct": float} — stages ordered and
    summed per spans.LEAF_STAGES; parent spans (e2e, dispatch.wall, ...)
    are reported but never counted toward coverage."""
    by_stage: dict[str, list[float]] = {}
    for name, _t0, dur_ns, *_ in span_rows:
        by_stage.setdefault(name, []).append(dur_ns / 1e6)
    e2e_p50 = _percentile(e2e_ms, 50)
    stages: dict[str, dict] = {}
    leaf_sum = 0.0
    for name in (*_spans.LEAF_STAGES, *_spans.PARENT_STAGES):
        durs = by_stage.pop(name, None)
        if not durs:
            continue
        p50 = _percentile(durs, 50)
        # a stage may fire more than once per wave (chunked device
        # batches, fast-path retry): charge its TOTAL per wave, not one
        # sample, or coverage undercounts exactly when it matters
        per_wave = p50 * (len(durs) / max(1, len(e2e_ms)))
        stages[name] = {
            "p50_ms": round(p50, 4),
            "p99_ms": round(_percentile(durs, 99), 4),
            "count": len(durs),
            "pct_of_e2e": round(100 * per_wave / e2e_p50, 1)
            if e2e_p50 > 0
            else 0.0,
        }
        if name in _spans.LEAF_STAGES:
            leaf_sum += per_wave
    for name, durs in sorted(by_stage.items()):  # ad-hoc span names
        stages[name] = {
            "p50_ms": round(_percentile(durs, 50), 4),
            "p99_ms": round(_percentile(durs, 99), 4),
            "count": len(durs),
            "pct_of_e2e": 0.0,
        }
    return {
        "e2e_ms": {
            "p50": round(e2e_p50, 3),
            "p99": round(_percentile(e2e_ms, 99), 3),
        },
        "waves": len(e2e_ms),
        "stages": stages,
        "coverage_pct": round(100 * leaf_sum / e2e_p50, 1)
        if e2e_p50 > 0
        else 0.0,
    }


def format_waterfall(result: dict) -> str:
    """The profile SUMMARY block (one section per QC size)."""
    lines = [
        "-" * 64,
        " PROFILE SUMMARY — verify-pipeline waterfall",
        f" Verifier: {result.get('verifier', '?')}  "
        f"route: {result.get('route', '?')}  "
        f"waves/size: {result.get('waves', '?')}",
        "-" * 64,
    ]
    for n, res in sorted(result["sizes"].items()):
        e2e = res["e2e_ms"]
        lines.append(
            f" QC size {n}: e2e p50 {e2e['p50']:.3f} ms, "
            f"p99 {e2e['p99']:.3f} ms"
        )
        lines.append(
            f"   {'stage':<15} {'p50 ms':>9} {'p99 ms':>9} "
            f"{'% e2e':>6}  waterfall"
        )
        for name in (*_spans.LEAF_STAGES, *_spans.PARENT_STAGES):
            st = res["stages"].get(name)
            if st is None:
                continue
            pct = st["pct_of_e2e"]
            bar = "#" * min(32, round(pct / 3.125)) if pct else ""
            tag = " (frame)" if name in _spans.PARENT_STAGES else ""
            lines.append(
                f"   {name:<15} {st['p50_ms']:>9.4f} {st['p99_ms']:>9.4f} "
                f"{pct:>5.1f}%  {bar}{tag}"
            )
        lines.append(
            f"   coverage: leaf-stage p50s account for "
            f"{res['coverage_pct']:.1f}% of e2e p50"
        )
        lines.append("")
    lines.append("-" * 64)
    return "\n".join(lines)


def run_profile(
    sizes=(16, 64, 256),
    waves: int = DEFAULT_WAVES,
    verifier: str = "tpu",
    route: str = "device",
    capture_dir: str | None = None,
) -> dict:
    """Drive the claim waves and return the per-size waterfall dict.

    ``route="device"`` pins warmed-up waves to the device via
    HOTSTUFF_FORCE_DEVICE_ROUTE (the waterfall should measure the
    dispatch pipeline, not the adaptive router's calls);
    ``route="auto"`` leaves the cost-model routing in charge.
    ``verifier="cpu"`` profiles the inline host path instead;
    ``verifier="bls"`` profiles the BLS claims path (device G1
    aggregation + host pairing equality per QC).
    """
    import asyncio

    from hotstuff_tpu import telemetry
    from hotstuff_tpu.crypto.async_service import AsyncVerifyService
    from hotstuff_tpu.crypto.service import CpuVerifier

    telemetry.enable()
    rec = _spans.enable()
    forced = verifier != "cpu" and route == "device"
    if forced:
        os.environ["HOTSTUFF_FORCE_DEVICE_ROUTE"] = "1"

    scheme = "bls" if verifier == "bls" else "ed25519"
    claims = {n: make_qc_claim(n, scheme=scheme) for n in sizes}
    out: dict = {
        "verifier": verifier,
        "route": route if verifier != "cpu" else "inline",
        "waves": waves,
        "sizes": {},
    }

    async def drive() -> None:
        if verifier == "cpu":
            svc = AsyncVerifyService(CpuVerifier())
        elif verifier == "bls":
            from hotstuff_tpu.crypto.async_service import eval_claims_sync
            from hotstuff_tpu.crypto.bls.service import BlsVerifier

            # device G1 vote-signature aggregation, host pairing — the
            # production BLS committee backend (crypto/scheme.py)
            backend = BlsVerifier(aggregator="tpu")
            backend.precompute(claims[max(sizes)][1])
            # warm every aggregation kernel shape through the claims
            # path (same cold-compile argument as the ed25519 branch)
            for n in sizes:
                assert eval_claims_sync(backend, [claims[n][0]]) == [True]
            backend.dispatch_deadline_s = 30.0
            svc = AsyncVerifyService(backend, device=True)
        else:
            from hotstuff_tpu.crypto.async_service import eval_claims_sync
            from hotstuff_tpu.node.node import LazyDeviceVerifier

            backend = LazyDeviceVerifier(verifier)
            backend.precompute(claims[max(sizes)][1])
            backend.warmup(batch=max(sizes))
            # warm EVERY padded kernel shape through the real dispatch
            # view: a cold XLA compile inside a measured wave would
            # overrun the dispatch deadline and demote the whole run to
            # the CPU fallback (observed: seconds per shape)
            for n in sizes:
                assert eval_claims_sync(backend.async_backend, [claims[n][0]]) == [True]
            # a slow simulated device (JAX_PLATFORMS=cpu) must still be
            # MEASURED, not deadline-demoted mid-profile
            backend.dispatch_deadline_s = 30.0
            svc = AsyncVerifyService(backend, device=True)
        # pre-compile every wave-bucket shape (no-op unless the backend
        # advertises wave padding): a measured wave must never pay the
        # cold XLA compile for its padded bucket
        svc.warm_buckets()
        try:
            for n in sizes:
                claim = claims[n][0]
                for _ in range(WARMUP_WAVES):
                    assert (await svc.verify_claims([claim])) == [True]
                rec.drain()  # warmup spans don't belong in the stats
                capture = (
                    capture_dir is not None
                    and verifier != "cpu"
                    and n == max(sizes)
                )
                if capture:
                    try:
                        import jax

                        jax.profiler.start_trace(capture_dir)
                    except Exception as exc:  # noqa: BLE001 — capture is
                        capture = False  # best-effort, never fatal
                        print(f"jax.profiler capture unavailable: {exc}")
                e2e: list[float] = []
                try:
                    for _ in range(waves):
                        t0 = time.perf_counter()
                        ok = await svc.verify_claims([claim])
                        e2e.append((time.perf_counter() - t0) * 1e3)
                        assert ok == [True], "profiled wave failed to verify"
                finally:
                    if capture:
                        import jax

                        jax.profiler.stop_trace()
                        print(f"jax.profiler trace written under {capture_dir}")
                out["sizes"][n] = waterfall(rec.drain(), e2e)
        finally:
            if svc.device:
                svc.close()

    try:
        asyncio.run(drive())
    finally:
        if forced:
            os.environ.pop("HOTSTUFF_FORCE_DEVICE_ROUTE", None)
    return out


def run_train(
    size: int = 256,
    train: int = DEFAULT_TRAIN_WAVES,
    reps: int = DEFAULT_TRAIN_REPS,
    depth: int | None = None,
    verifier: str = "tpu",
) -> dict:
    """Sustained wave-train mode (ISSUE 5): drive ``train``
    distinct-digest QC waves BACK TO BACK through the dispatch pipeline
    and compare the amortized per-wave latency against the sequential
    single-wave p50 — overlap efficiency is the share of the per-wave
    round trip the staging/execute overlap hides.  Runs at depth 1 (the
    old single-in-flight behavior) and at ``depth`` (default:
    HOTSTUFF_VERIFY_PIPELINE) so the comparison is self-contained."""
    import asyncio

    from hotstuff_tpu.crypto.async_service import (
        AsyncVerifyService,
        eval_claims_sync,
        pipeline_depth_from_env,
    )

    depth = depth or pipeline_depth_from_env()
    scheme = "bls" if verifier == "bls" else "ed25519"
    claims, pks = make_train_claims(size, train, scheme=scheme)
    os.environ["HOTSTUFF_FORCE_DEVICE_ROUTE"] = "1"
    out: dict = {
        "verifier": verifier,
        "qc_size": size,
        "train_waves": train,
        "reps": reps,
        "depths": {},
    }

    if verifier == "bls":
        from hotstuff_tpu.crypto.bls.service import BlsVerifier

        backend = BlsVerifier(aggregator="tpu")
        backend.precompute(pks)
        assert eval_claims_sync(backend, [claims[0]]) == [True]
        backend.dispatch_deadline_s = 30.0
    else:
        from hotstuff_tpu.node.node import LazyDeviceVerifier

        backend = LazyDeviceVerifier(verifier)
        backend.precompute(pks)
        backend.warmup(batch=size)
        assert eval_claims_sync(backend.async_backend, [claims[0]]) == [True]
        # a slow simulated device must be MEASURED, not deadline-demoted
        backend.dispatch_deadline_s = 30.0

    async def drive(d: int) -> dict:
        svc = AsyncVerifyService(backend, device=True, pipeline_depth=d)
        svc.warm_buckets()
        try:
            for _ in range(WARMUP_WAVES):
                assert (await svc.verify_claims([claims[0]])) == [True]
            # singles: sequential fully-awaited waves — zero overlap,
            # the baseline the train amortization is measured against
            singles: list[float] = []
            for claim in claims:
                t0 = time.perf_counter()
                assert (await svc.verify_claims([claim])) == [True]
                singles.append((time.perf_counter() - t0) * 1e3)
            # trains: each wave submitted as its OWN dispatch (yield
            # until the dispatcher has taken the pending submission
            # before staging the next), whole train awaited at once
            trains: list[float] = []
            for _ in range(reps):
                t0 = time.perf_counter()
                futs = []
                for claim in claims:
                    futs.append(
                        asyncio.ensure_future(svc.verify_claims([claim]))
                    )
                    await asyncio.sleep(0)
                    while svc._pending:
                        await asyncio.sleep(0)
                results = await asyncio.gather(*futs)
                trains.append((time.perf_counter() - t0) * 1e3)
                assert all(r == [True] for r in results), "train wave failed"
            single_p50 = _percentile(singles, 50)
            train_p50 = _percentile(trains, 50)
            return {
                "single_wave_p50_ms": round(single_p50, 3),
                "train_p50_ms": round(train_p50, 3),
                "amortized_wave_ms": round(train_p50 / train, 3),
                "peak_inflight": svc.peak_inflight,
                "pipeline_waits": svc.pipeline_waits,
                "train_sigs_per_s": round(
                    size * train / (train_p50 / 1e3), 1
                )
                if train_p50 > 0
                else 0.0,
            }
        finally:
            svc.close()

    try:
        for d in sorted({1, depth}):
            out["depths"][d] = asyncio.run(drive(d))
    finally:
        os.environ.pop("HOTSTUFF_FORCE_DEVICE_ROUTE", None)
    base = out["depths"].get(1)
    top = out["depths"].get(depth)
    if base and top and depth > 1 and top["amortized_wave_ms"] > 0:
        out["overlap_speedup"] = round(
            base["amortized_wave_ms"] / top["amortized_wave_ms"], 3
        )
        out["overlap_efficiency_pct"] = round(
            100.0
            * (1 - top["amortized_wave_ms"] / base["amortized_wave_ms"]),
            1,
        )
    return out


def format_train(result: dict) -> str:
    """The wave-train SUMMARY block (one row per pipeline depth)."""
    lines = [
        "-" * 64,
        " PROFILE SUMMARY — sustained verify wave-train",
        f" Verifier: {result['verifier']}  QC size {result['qc_size']}  "
        f"{result['train_waves']} waves/train x {result['reps']} trains",
        "-" * 64,
        f"   {'depth':>5} {'single p50':>12} {'train p50':>11} "
        f"{'amortized':>11} {'peak':>5} {'sigs/s':>9}",
    ]
    for d, res in sorted(result["depths"].items()):
        lines.append(
            f"   {d:>5} {res['single_wave_p50_ms']:>10.3f}ms "
            f"{res['train_p50_ms']:>9.3f}ms "
            f"{res['amortized_wave_ms']:>9.3f}ms {res['peak_inflight']:>5} "
            f"{res['train_sigs_per_s']:>9.0f}"
        )
    if "overlap_speedup" in result:
        top = max(result["depths"])
        lines.append(
            f"   overlap: depth-{top} amortized wave is "
            f"{result['overlap_speedup']:.2f}x depth-1 "
            f"({result['overlap_efficiency_pct']:.1f}% of the per-wave "
            "round trip hidden by staging/execute overlap)"
        )
    lines.append("-" * 64)
    return "\n".join(lines)


__all__ = [
    "run_profile",
    "run_train",
    "waterfall",
    "format_waterfall",
    "format_train",
    "make_qc_claim",
    "make_train_claims",
    "DEFAULT_WAVES",
    "DEFAULT_TRAIN_WAVES",
]
