#!/usr/bin/env python3
"""Zero-copy ingest check (ISSUE 20): do vote frames arriving on the
native transport actually verify from the staging arenas?

End-to-end harness over the production pieces: a native reactor
listener (``network/native.py`` -> ``dispatch_ingest`` packing tag-1
frames into the wave arenas), a vote-decoding handler submitting claim
waves to the device ``AsyncVerifyService``, and real signed votes sent
open-loop through ``NativeSimpleSender``.  Every wave the service
serves should adopt its columns straight from the arena the reactor
packed — the flatten/prepare copies the zero-copy path exists to erase.

Asserts:
  - every verdict is True (adoption must not corrupt columns),
  - the zero-copy hit rate (adopted waves / submitted vote waves) is
    >= ``--min-hit`` (default 0.90) — below that the pack stream is
    desyncing from the claim stream and the fast path is decorative,
  - reports end-to-end sigs/s (wire -> verdict) for the bench record.

Skip-if-unsupported: without the native toolchain (libhs_transport.so
unbuildable) there is nothing to check — prints SKIP and exits 0, same
contract as scripts/san_check.py.

Usage:
    python scripts/ingest_check.py               # default 24 x 256
    INGEST=1 scripts/trace.sh                    # via the trace wrapper
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


class WaveHandler:
    """Decodes vote frames, submits fixed-size claim waves."""

    def __init__(self, svc, wave_size: int):
        self.svc = svc
        self.wave_size = wave_size
        self.claims: list = []
        self.tasks: list = []
        self.waves = 0
        self.warmed = asyncio.Event()

    async def dispatch(self, writer, message: bytes) -> None:
        from hotstuff_tpu.consensus.wire import TAG_VOTE, decode_message

        tag, payload = decode_message(bytes(message), scheme="ed25519")
        if tag != TAG_VOTE:
            # the producer-v2 handshake frame: proves the sender's
            # connection is live before the open-loop vote stream starts
            self.warmed.set()
            return
        self.claims.append(payload.claim())
        if len(self.claims) >= self.wave_size:
            wave, self.claims = self.claims, []
            self.waves += 1
            self.tasks.append(
                asyncio.ensure_future(self.svc.verify_claims(wave))
            )


def make_votes(count: int, signers: int):
    """``count`` distinct signed votes round-robined over ``signers``
    keypairs; returns (wire frames, signer pubkey bytes)."""
    from hotstuff_tpu.consensus.messages import Vote
    from hotstuff_tpu.consensus.wire import encode_vote
    from hotstuff_tpu.crypto import Digest, Signature, generate_keypair

    keys = [
        generate_keypair(bytes([7 + i]) * 32, i) for i in range(signers)
    ]
    frames = []
    for i in range(count):
        pk, sk = keys[i % signers]
        vote = Vote(
            hash=Digest.of(b"ingest_check block %d" % i),
            round=i + 1,
            author=pk,
        )
        vote.signature = Signature.new(vote.digest(), sk)
        frames.append(encode_vote(vote))
    return frames, [pk.to_bytes() for pk, _ in keys]


async def run(args) -> int:
    from hotstuff_tpu.consensus.wire import encode_producer_batch
    from hotstuff_tpu.crypto.async_service import AsyncVerifyService
    from hotstuff_tpu.crypto.digest import Digest
    from hotstuff_tpu.network import native
    from hotstuff_tpu.node.node import LazyDeviceVerifier

    from tests.common import fresh_base_port

    total = args.waves * args.wave_size
    print(
        f" building {total} signed votes "
        f"({args.waves} waves x {args.wave_size})..."
    )
    frames, pubkeys = make_votes(total, signers=4)

    backend = LazyDeviceVerifier("tpu")
    backend.precompute(pubkeys)
    backend.warmup(batch=args.wave_size)
    # the simulated device (JAX_PLATFORMS=cpu) is slow but must stay
    # measured, not deadline-demoted mid-check
    backend.dispatch_deadline_s = 30.0
    svc = AsyncVerifyService(backend, device=True)
    svc.warm_buckets()

    handler = WaveHandler(svc, args.wave_size)
    port = fresh_base_port()
    recv = native.NativeReceiver("127.0.0.1", port, handler)
    await recv.spawn()
    sender = native.NativeSimpleSender()
    addr = ("127.0.0.1", port)

    try:
        # connect handshake: the native sender drops frames while the
        # connection is still in flight, and a dropped VOTE would desync
        # pack and claim streams — so prove liveness with a frame the
        # packer ignores (tag 6) before any vote leaves
        ping = encode_producer_batch([(Digest.of(b"ingest ping"), b"")])
        for _ in range(100):
            await sender.send(addr, ping)
            try:
                await asyncio.wait_for(handler.warmed.wait(), timeout=0.1)
                break
            except asyncio.TimeoutError:
                continue
        if not handler.warmed.is_set():
            print("ingest_check: FAIL (native sender never connected)")
            return 1

        # paced open loop: at most two waves outstanding, like a real
        # committee where vote arrival tracks commit rate.  A flat-out
        # flood would just overflow the staging arena (the largest
        # wave bucket's rows) and measure the resync path, not the
        # steady state.
        t0 = time.perf_counter()
        deadline = time.monotonic() + args.timeout
        for w in range(args.waves):
            base = w * args.wave_size
            for frame in frames[base:base + args.wave_size]:
                await sender.send(addr, frame)
            while handler.waves <= w:
                if time.monotonic() > deadline:
                    print(
                        f"ingest_check: FAIL (only {handler.waves}/"
                        f"{args.waves} waves arrived before timeout)"
                    )
                    return 1
                await asyncio.sleep(0.005)
            if w >= 2:
                await asyncio.wait_for(
                    asyncio.shield(handler.tasks[w - 2]),
                    timeout=args.timeout,
                )
        results = await asyncio.wait_for(
            asyncio.gather(*handler.tasks), timeout=args.timeout
        )
        elapsed = time.perf_counter() - t0
    finally:
        sender.close()
        await recv.shutdown()
        svc.close()

    verdicts = [v for wave in results for v in wave]
    bad = verdicts.count(False)
    zc, fb = svc.zero_copy_waves, svc.fallback_waves
    # sig-based hit rate: the dispatcher may coalesce several submitted
    # waves into one adoption, so wave counts under-report coverage
    hit = svc.zero_copy_sigs / len(verdicts) if verdicts else 0.0
    sigs_per_s = len(verdicts) / elapsed if elapsed > 0 else 0.0

    print(" INGEST CHECK — wire -> arena -> device, no flatten copies")
    print(
        f"   waves: {handler.waves} submitted, {zc} adopted zero-copy, "
        f"{fb} fell back"
    )
    print(
        f"   sigs:  {svc.zero_copy_sigs}/{len(verdicts)} verified from "
        f"arenas ({100 * hit:.1f}% zero-copy hit rate)"
    )
    print(
        f"   rate:  {len(verdicts)} sigs in {elapsed:.2f} s "
        f"-> {sigs_per_s:,.0f} e2e sigs/s (simulated device)"
    )

    failures = []
    if bad:
        failures.append(f"{bad} valid votes got a False verdict")
    if hit < args.min_hit:
        failures.append(
            f"zero-copy hit rate {100 * hit:.1f}% < "
            f"{100 * args.min_hit:.0f}% — pack/claim streams desynced"
        )
    if failures:
        print("ingest_check: FAIL")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    print("ingest_check: OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--waves", type=int, default=24,
                    help="vote waves to send (default 24)")
    ap.add_argument("--wave-size", type=int, default=256,
                    help="votes per wave (default 256)")
    ap.add_argument("--min-hit", type=float, default=0.90,
                    help="minimum zero-copy hit rate (default 0.90)")
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="end-to-end deadline in seconds (default 120)")
    args = ap.parse_args(argv)

    from hotstuff_tpu.crypto import native_ed25519

    if not native_ed25519.wave_pack_available():
        print(
            "ingest_check: SKIP (native toolchain unavailable — "
            "cannot build libhs_transport.so)"
        )
        return 0
    try:
        from hotstuff_tpu.network import native  # noqa: F401
    except Exception as exc:  # pragma: no cover - same toolchain
        print(f"ingest_check: SKIP (native transport unavailable: {exc})")
        return 0

    return asyncio.run(run(args))


if __name__ == "__main__":
    sys.exit(main())
