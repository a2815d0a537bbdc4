"""``HOTSTUFF_NO_CLAIM_DEDUP=1``: one shared dispatch stream, nothing
deduplicated (the ``colo64.nodedup`` deployment, ISSUE 28).

Every co-located core's claims still coalesce into one wave, but the
wave's lanes are the submitted signatures: each submitter's verdicts
are read from its own lanes and equal ``crypto/ed25519_ref``'s for its
own claims, a corrupt copy fails for the submitter that handed it in
and for no other, a wave past the largest bucket is cut into chunks
whose verdicts come back in order, and an 8-node committee in this
process commits with ``submitted_sigs`` = evaluated on its stats line.
"""

import asyncio
import itertools
import logging
import random
import time

import pytest

from benchmark.invariants import check_safety
from chipbench.logs import CommitteeLog
from hotstuff_tpu.consensus import Consensus, Parameters
from hotstuff_tpu.crypto import (
    Digest,
    Signature,
    SignatureService,
    ed25519_ref,
    generate_keypair,
)
from hotstuff_tpu.crypto.async_service import (
    DEFAULT_WAVE_BUCKETS,
    AsyncVerifyService,
)
from hotstuff_tpu.crypto.service import CpuVerifier
from hotstuff_tpu.store import Store

from .common import async_test, committee, keys

VARIABLE = "HOTSTUFF_NO_CLAIM_DEDUP"
SUBMITTERS = 8
QC_VOTES = 5

# a range of its own (tests/test_relay.py says why), above that file's
# and above the xdist workers' (tests/common.py: up to 31,999), and
# below the ephemeral range (32,768 up): at 34,000 a listener met the
# source port of a connection of the 50-node rehearsal running beside
# it (tests/chipbench/test_wan50_cell.py opens some 5,000)
_ports = itertools.count(32_000, 20)
_kinds = itertools.count()

_ref_memo: dict[tuple, bool] = {}


def ref_verdict(claim) -> bool:
    """The claim's verdict by ``crypto/ed25519_ref`` alone (5 ms a
    signature, so each distinct signature is verified once)."""
    rows = (
        [(claim[1], claim[2], claim[3])]
        if claim[0] == "one"
        else [(claim[1], pk, sig) for pk, sig in claim[2]]
    )
    for row in rows:
        if row not in _ref_memo:
            digest, pk, sig = row
            _ref_memo[row] = ed25519_ref.verify(sig, pk, digest)
    return bool(rows) and all(_ref_memo[row] for row in rows)


class DeviceHost(CpuVerifier):
    """A device-kind backend over the CPU verifier: the service gives it
    the coalescing, off-loop dispatch path, and it records the rows of
    every call its device view is handed."""

    device_ready = True
    supports_wave_padding = True
    #: a loaded test machine must not turn a wave into a deadline miss
    dispatch_deadline_s = 5.0

    def __init__(self):
        self.async_kind = f"nodedup-test-{next(_kinds)}"
        self.calls: list[int] = []
        host = self

        class View:
            def verify_many(self, digests, pks, sigs, aggregate_ok=False):
                host.calls.append(len(digests))
                return CpuVerifier().verify_many(digests, pks, sigs)

        self.async_backend = View()


def vote_claim(seed: int, digest: Digest, spoil: bool = False) -> tuple:
    pk, sk = generate_keypair(b"\x1c" * 32, seed)
    signed = Digest.of(b"another digest") if spoil else digest
    return (
        "one", digest.to_bytes(), pk.to_bytes(),
        Signature.new(signed, sk).to_bytes(),
    )


def qc_claim(digest: Digest, spoil_vote: int | None = None) -> tuple:
    votes = []
    for i in range(QC_VOTES):
        claim = vote_claim(100 + i, digest, spoil=i == spoil_vote)
        votes.append((claim[2], claim[3]))
    return ("shared", digest.to_bytes(), tuple(votes))


def overlapping_submissions(seed: int) -> list[list]:
    """What 8 co-located cores hand in for one broadcast: the same QC
    claim from all, a vote claim of its own from each, and corrupt
    signatures among them, where the seed puts them: a corrupt copy of
    the QC in two submitters' lists (the others hold the sound one), a
    spoiled vote in two."""
    rng = random.Random(seed)
    digest = Digest.of(b"nodedup wave %d" % seed)
    bad_qc = set(rng.sample(range(SUBMITTERS), 2))
    bad_vote = set(rng.sample(range(SUBMITTERS), 2))
    return [
        [
            qc_claim(digest, spoil_vote=rng.randrange(QC_VOTES))
            if i in bad_qc
            else qc_claim(digest),
            vote_claim(i, Digest.of(b"vote %d/%d" % (seed, i)),
                       spoil=i in bad_vote),
        ]
        for i in range(SUBMITTERS)
    ]


async def hand_in(service, submissions):
    return await asyncio.gather(
        *(service.verify_claims(claims) for claims in submissions)
    )


def test_one_shared_service_nothing_deduplicated(monkeypatch):
    """The variable keeps the one service a (loop, kind) and turns the
    dedup off in it: eight copies of one claim ride one wave on eight
    lanes (it used to give each core a private service)."""
    monkeypatch.setenv(VARIABLE, "1")
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    monkeypatch.setenv("HOTSTUFF_WAVE_BUCKETS", "off")
    host = DeviceHost()
    claim = vote_claim(0, Digest.of(b"one claim"))

    async def drive():
        first = AsyncVerifyService.for_backend(host)
        second = AsyncVerifyService.for_backend(host)
        try:
            outs = await hand_in(first, [[claim]] * SUBMITTERS)
        finally:
            first.close()
        return first, second, outs

    first, second, outs = asyncio.run(drive())
    assert first is second and first.device
    assert outs == [[True]] * SUBMITTERS
    assert host.calls == [SUBMITTERS]
    assert first.device_dispatches == 1
    assert first.submitted_sigs == first.device_sigs == SUBMITTERS


@pytest.mark.parametrize("seed", [28, 2_147_483_999])
@pytest.mark.parametrize("variable", ["1", None], ids=["nodedup", "dedup"])
@async_test
async def test_each_submitter_gets_the_reference_verdicts_of_its_own_claims(
    monkeypatch, variable, seed
):
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    if variable is None:
        monkeypatch.delenv(VARIABLE, raising=False)
    else:
        monkeypatch.setenv(VARIABLE, variable)
    submissions = overlapping_submissions(seed)
    submitted = SUBMITTERS * (QC_VOTES + 1)
    host = DeviceHost()
    service = AsyncVerifyService.for_backend(host)
    try:
        outs = await hand_in(service, submissions)
    finally:
        service.close()
    assert outs == [[ref_verdict(c) for c in cs] for cs in submissions]
    assert not all(v for out in outs for v in out)  # the corrupt ones failed
    assert service.device_dispatches == 1 and service.cpu_dispatches == 0
    assert service.submitted_sigs == submitted
    distinct = {c for cs in submissions for c in cs}
    evaluated = (
        submitted
        if variable
        else sum(QC_VOTES if c[0] == "shared" else 1 for c in distinct)
    )
    assert service.device_sigs == evaluated
    # one call, padded to the smallest bucket that holds the wave
    lanes = next(b for b in DEFAULT_WAVE_BUCKETS if b >= evaluated)
    assert host.calls == [lanes]
    assert (service.lanes, service.chunks) == (lanes, 1)


@async_test
async def test_a_corrupt_copy_fails_for_its_submitter_alone(monkeypatch):
    """Two cores hand in claims that differ in one signature byte: with
    the dedup off each reads its own lanes, whatever the other's say."""
    monkeypatch.setenv(VARIABLE, "1")
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    digest = Digest.of(b"a broadcast block")
    sound = qc_claim(digest)
    votes = list(sound[2])
    pk, sig = votes[2]
    votes[2] = (pk, bytes([sig[0] ^ 1]) + sig[1:])
    corrupt = ("shared", sound[1], tuple(votes))
    submissions = [[sound]] * 3 + [[corrupt]] + [[sound]] * 4
    service = AsyncVerifyService.for_backend(DeviceHost())
    try:
        outs = await hand_in(service, submissions)
    finally:
        service.close()
    assert outs == [[True]] * 3 + [[False]] + [[True]] * 4
    assert ref_verdict(sound) and not ref_verdict(corrupt)


def single_votes(n: int, spoiled: set[int]) -> list:
    digest = Digest.of(b"an oversized wave")
    return [vote_claim(i % 64, digest, spoil=i in spoiled) for i in range(n)]


def timed_path_wave(n: int, spoiled: set[int]) -> list:
    """``n`` cores' copies of one proposal: a 43-vote QC claim and the
    block's signature each, as ``colo64.nodedup`` forms its wave; the
    ``spoiled`` cores hold a corrupt copy of the QC."""
    digest = Digest.of(b"the timed path")
    pairs = [vote_claim(i, digest) for i in range(43)]
    sound = ("shared", digest.to_bytes(), tuple((c[2], c[3]) for c in pairs))
    bad = vote_claim(0, digest, spoil=True)
    corrupt = ("shared", sound[1], ((bad[2], bad[3]),) + sound[2][1:])
    block = vote_claim(50, Digest.of(b"the block"))
    out = []
    for i in range(n):
        out += [corrupt if i in spoiled else sound, block]
    return out


LARGEST = DEFAULT_WAVE_BUCKETS[-1]


@pytest.mark.parametrize(
    "make, spoiled, calls, chunk_sigs",
    [
        # 2 x the largest bucket + 1 single signatures: two full chunks
        # and one of a single lane, padded to the smallest bucket
        (
            lambda spoiled: single_votes(2 * LARGEST + 1, spoiled),
            {0, LARGEST - 1, LARGEST, 2 * LARGEST - 1, 2 * LARGEST},
            [LARGEST, LARGEST, DEFAULT_WAVE_BUCKETS[0]],
            [LARGEST, LARGEST, 1],
        ),
        # the timed path's wave: 64 x (43 + 1) = 2,816 signatures, cut
        # between claims: 23 cores' 1,012 signatures a chunk, then 18's
        (
            lambda spoiled: timed_path_wave(64, spoiled),
            {0, 22, 23, 45, 46, 63},
            [LARGEST] * 3,
            [1012, 1012, 792],
        ),
    ],
    ids=["2x1024+1", "64x44"],
)
@async_test
async def test_an_oversized_wave_is_chunked_and_its_verdicts_keep_their_order(
    monkeypatch, make, spoiled, calls, chunk_sigs
):
    claims = make(spoiled)
    monkeypatch.setenv(VARIABLE, "1")
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    monkeypatch.delenv("HOTSTUFF_WAVE_BUCKETS", raising=False)
    host = DeviceHost()
    service = AsyncVerifyService.for_backend(host)
    try:
        out = await service.verify_claims(claims)
    finally:
        service.close()
    assert out == [ref_verdict(c) for c in claims]
    assert out.count(False) == len(spoiled)
    assert host.calls == calls
    assert service.device_dispatches == 1 and service.deadline_misses == 0
    assert (service.lanes, service.chunks) == (sum(calls), len(calls))
    assert service.device_sigs == service.submitted_sigs == sum(chunk_sigs)
    assert service.pad_sigs == sum(calls) - sum(chunk_sigs)


def test_the_deadline_grows_with_the_chunks():
    """A wave of three backend calls gets three calls' floor; the
    one-call wave keeps the floor it had."""
    service = AsyncVerifyService(DeviceHost(), device=True)
    floor = DeviceHost.dispatch_deadline_s
    try:
        assert service._deadline_s() == service._deadline_s(1) == floor
        assert service._deadline_s(3) == 3 * floor
        service._device_ewma_s = floor  # four EWMAs outlast three floors
        assert service._deadline_s(3) == pytest.approx(4 * floor)
    finally:
        service.close()


def test_wave_serials_are_one_counter_a_process(monkeypatch):
    """Two services of one process never give two waves one serial: a
    trace joins a wave's spans on ``wave=<serial>`` alone."""
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    claim = vote_claim(1, Digest.of(b"serials"))

    async def drive():
        services = [
            AsyncVerifyService(DeviceHost(), device=True) for _ in range(2)
        ]
        serials = []
        try:
            for _ in range(3):
                for service in services:
                    await service.verify_claims([claim])
                    serials.append(service._wave_serial)
        finally:
            for service in services:
                service.close()
        return serials

    serials = asyncio.run(drive())
    assert len(set(serials)) == 6 and serials == sorted(serials)


# ---- a committee of eight in this process --------------------------------

N = 8


class StatsLines(logging.Handler):
    """The service's stats lines, as the committee's log would hold them."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.lines: list[str] = []

    def emit(self, record):
        message = record.getMessage()
        if message.startswith("Verify service stats"):
            self.lines.append(
                f"2026-01-01T00:00:00.000Z [INFO] {record.name} {message}"
            )


@async_test
async def test_eight_nodes_commit_with_every_submitted_signature_evaluated(
    tmp_path, monkeypatch
):
    monkeypatch.setenv(VARIABLE, "1")
    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    host = DeviceHost()
    stats = StatsLines()
    logger = logging.getLogger("hotstuff_tpu.crypto.async_service")
    old_level = logger.level
    logger.setLevel(logging.INFO)
    logger.addHandler(stats)
    com = committee(next(_ports), N)
    nodes = []
    for name, secret in keys(N):
        store = Store(str(tmp_path / f"db_{len(nodes)}"))
        commits: asyncio.Queue = asyncio.Queue()
        stack = await Consensus.spawn(
            name, com, Parameters(timeout_delay=5_000, sync_retry_delay=5_000),
            SignatureService(secret), store, commits, verifier=host,
            bind_host="127.0.0.1",
        )
        nodes.append((str(name)[:8], stack, commits, store))
    service = nodes[0][1].core.averifier
    assert all(stack.core.averifier is service for _, stack, _, _ in nodes)

    async def feed():
        for k in range(10_000):
            digest = Digest.of(b"nodedup payload %d" % k)
            await nodes[k % N][1].tx_producer.put(digest)
            await asyncio.sleep(0.005)

    feeder = asyncio.ensure_future(feed())
    commits_by_node: dict[str, list] = {name: [] for name, _, _, _ in nodes}
    fed: set[str] = set()
    try:
        deadline = time.monotonic() + 60.0
        # until every node has committed twenty blocks that carry payloads
        while time.monotonic() < deadline and not all(
            sum(bool(payloads) for _, _, _, payloads in commits) >= 20
            for commits in commits_by_node.values()
        ):
            await asyncio.sleep(0.05)
            for name, _, commits, _ in nodes:
                while not commits.empty():
                    block = commits.get_nowait()
                    commits_by_node[name].append(
                        (time.time(), block.round, str(block.digest()),
                         block.payloads)
                    )
                    fed.update(str(d) for d in block.payloads)
        # the counters as the next stats line would print them
        service._next_stats_log = 0.0
        service._log_stats()
    finally:
        feeder.cancel()
        for _, stack, _, _ in nodes:
            await stack.shutdown()
        for _, _, _, store in nodes:
            store.close()
        logger.removeHandler(stats)
        logger.setLevel(old_level)

    assert all(
        sum(bool(p) for _, _, _, p in commits) >= 20
        for commits in commits_by_node.values()
    ), {name: len(c) for name, c in commits_by_node.items()}
    ok, violations = check_safety(
        {
            name: [(t, rnd, digest) for t, rnd, digest, _ in commits]
            for name, commits in commits_by_node.items()
        }
    )
    assert ok, violations
    for commits in commits_by_node.values():
        payloads = [str(d) for _, _, _, ps in commits for d in ps]
        assert len(payloads) == len(set(payloads))  # none committed twice
    log = CommitteeLog()
    log.feed("\n".join(stats.lines))
    total = log.stats_at(float("inf"))
    assert total["submitted_sigs"] == total["device_sigs"] + total["cpu_sigs"]
    assert total["submitted_sigs"] > 0
    # every one of the 8 cores had the proposals' certificates verified
    # for itself: more than the distinct signatures of the committed chain
    quorum = 2 * ((N - 1) // 3) + 1
    blocks = max(len(c) for c in commits_by_node.values())
    assert total["device_sigs"] > 4 * blocks * (quorum + 1)
    assert total["lanes"] >= total["device_sigs"]
    assert total["chunks"] == total["device"]
