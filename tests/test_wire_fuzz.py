"""Wire-codec robustness: random and mutated frames must never crash the
decoder — only ``SerializationError`` (or a clean decode) is acceptable.

The reference has no fuzzing at all (SURVEY §4 lists it as a gap); the
receiver dispatch feeds raw unauthenticated TCP frames into
``decode_message``, so "any byte string produces either a message or a
clean error" is a load-bearing property for liveness under garbage.
"""

from __future__ import annotations

import random

import pytest

from hotstuff_tpu.consensus.errors import SerializationError
from hotstuff_tpu.consensus.messages import MAX_BLOCK_PAYLOADS
from hotstuff_tpu.consensus.wire import (
    decode_message,
    encode_propose,
    encode_sync_request,
    encode_tc,
    encode_timeout,
    encode_vote,
)
from hotstuff_tpu.crypto import Digest, Signature

from .common import (
    chain,
    keys,
    qc_for_block,
    secret_for,
    signed_timeout,
    signed_vote,
)


def _decode_must_not_crash(data: bytes) -> None:
    try:
        decode_message(data)
    except SerializationError:
        pass  # the only acceptable failure mode


def test_random_frames_never_crash():
    rng = random.Random(0xF022)
    for _ in range(2_000):
        n = rng.randrange(0, 200)
        _decode_must_not_crash(rng.randbytes(n))


def test_tag_prefixed_random_frames_never_crash():
    """Valid tags followed by garbage exercise each decoder's depths."""
    rng = random.Random(0xF023)
    for tag in range(8):  # includes unknown tags
        for _ in range(500):
            body = rng.randbytes(rng.randrange(0, 400))
            _decode_must_not_crash(bytes([tag]) + body)


def test_mutated_valid_frames_never_crash():
    """Single-byte mutations and truncations of genuine messages — the
    most reachable malformed inputs for a Byzantine peer."""
    rng = random.Random(0xF024)
    blocks = chain(3)
    pk, sk = keys()[0]
    frames = [
        encode_propose(blocks[-1]),
        encode_vote(signed_vote(blocks[1], pk, sk)),
        encode_timeout(signed_timeout(qc_for_block(blocks[1]), 5, pk, sk)),
        encode_sync_request(Digest.of(b"missing"), pk),
    ]
    from hotstuff_tpu.consensus.messages import TC, timeout_digest
    from hotstuff_tpu.crypto import Signature

    tc = TC(
        round=5,
        votes=[
            (p, Signature.new(timeout_digest(5, 0), s), 0)
            for p, s in keys()[:3]
        ],
    )
    frames.append(encode_tc(tc))

    for frame in frames:
        decode_message(frame)  # sanity: the originals decode
        for _ in range(300):
            buf = bytearray(frame)
            pos = rng.randrange(len(buf))
            buf[pos] ^= 1 << rng.randrange(8)
            _decode_must_not_crash(bytes(buf))
        for cut in range(0, len(frame), max(1, len(frame) // 40)):
            _decode_must_not_crash(frame[:cut])
            _decode_must_not_crash(frame + frame[:cut])  # trailing junk


def test_length_field_extremes_never_crash_or_overallocate():
    """Huge declared counts/lengths must be rejected by caps, not
    attempted as allocations."""
    import struct

    # Propose frame claiming 2^32-1 payloads
    from hotstuff_tpu.utils.codec import Encoder

    enc = Encoder().u8(0)
    blocks = chain(2)
    blocks[-1].qc.encode(enc)
    enc.flag(False)
    from hotstuff_tpu.consensus.messages import encode_pk

    encode_pk(enc, blocks[-1].author)
    enc.u64(blocks[-1].round)
    enc.u32(0xFFFFFFFF)  # payload count
    _decode_must_not_crash(enc.finish())
    # vote whose pk length prefix is absurd
    frame = bytes([1]) + b"\x00" * 32 + struct.pack("<Q", 1) + struct.pack(
        "<I", 1 << 30
    )
    _decode_must_not_crash(frame)
    # block payload count just over the protocol cap decodes (the cap is
    # a VERIFY-time rule) or errors cleanly — never crashes
    assert MAX_BLOCK_PAYLOADS == 512


def test_adversarial_well_formed_frames_decode_then_fail_verify():
    """Frames an adversary-plane node actually emits (faults/adversary.py)
    are WELL-FORMED on the wire — they must decode cleanly and be killed
    by verification (``ConsensusError``), never by the codec and never by
    an unhandled crash.  This is a different threat than random mutation:
    every byte here is chosen by a protocol-aware attacker."""
    import time

    from hotstuff_tpu.consensus.errors import ConsensusError
    from hotstuff_tpu.crypto.service import CpuVerifier
    from hotstuff_tpu.faults.adversary import AdversaryPlane

    from .common import committee, signed_block

    base = 9_900
    com = committee(base)
    verifier = CpuVerifier()
    plane = AdversaryPlane(
        {
            "name": "byz-forge-qc",
            "seed": 7,
            "epoch_unix": time.time(),
            "nodes": {f"127.0.0.1:{base + i}": i for i in range(4)},
            "adversary": [{"policy": "forge-qc", "node": 0, "at": 0.0}],
        },
        ("127.0.0.1", base),
    )
    pairs = keys()
    blocks = chain(3)

    # 1. Forged QC smuggled inside an otherwise-genuine timeout: passes
    #    check_weight (real authors, quorum-many), decode round-trips,
    #    verification rejects the garbage signatures.
    forged = plane.forged_qc(com, blocks[1].round)
    forged.check_weight(com)
    pk, sk = pairs[0]
    frame = encode_timeout(signed_timeout(forged, 5, pk, sk))
    _, timeout = decode_message(frame)
    with pytest.raises(ConsensusError):
        timeout.verify(com, verifier)

    # 2. The forged QC as a block's parent certificate.
    author, secret = pairs[2 % 4]
    bad_block = signed_block(author, secret, 2, qc=forged)
    _, decoded = decode_message(encode_propose(bad_block))
    assert decoded.digest() == bad_block.digest()
    with pytest.raises(ConsensusError):
        decoded.verify(com, verifier)

    # 3. A vote whose signature was produced by a DIFFERENT committee
    #    member (signature spoofing a peer): structurally perfect, fails
    #    only on crypto.
    spoofed = signed_vote(blocks[1], pairs[1][0], pairs[2][1])
    _, vote = decode_message(encode_vote(spoofed))
    with pytest.raises(ConsensusError):
        vote.verify(com, verifier)

    # 4. The equivocating twin of a committed block, genuinely signed by
    #    its author: decodes AND verifies — only the safety rule (not the
    #    codec or crypto) can reject it, which is exactly why the
    #    invariant checker needs attribution.
    shadow = plane.shadow_block(blocks[1])
    shadow.signature = Signature.new(
        shadow.digest(), secret_for(shadow.author)
    )
    _, twin = decode_message(encode_propose(shadow))
    twin.verify(com, verifier)
    assert twin.digest() != blocks[1].digest()
    assert twin.round == blocks[1].round and twin.author == blocks[1].author

    # 5. Mutations of the adversarial frames still never crash the codec.
    rng = random.Random(0xF025)
    for f in (frame, encode_propose(bad_block), encode_vote(spoofed)):
        for _ in range(200):
            buf = bytearray(f)
            buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
            _decode_must_not_crash(bytes(buf))


# ---------------------------------------------------------------------------
# decode-time count caps (ISSUE 12): the wire-decoder-bounds lint rule
# flagged the QC/TC vote-count and block payload-count reads as
# unbounded — a forged 4-byte count could size a decode loop before any
# truncation check fired.  The caps added for it must reject the count
# itself, before the first element decode or allocation.


def test_vote_count_bombs_die_in_the_codec():
    from hotstuff_tpu.consensus.messages import MAX_CERT_VOTES
    from hotstuff_tpu.utils.codec import Encoder

    # the cap matches the signer-bitmap member ceiling: no committee the
    # compact form can name could ever produce more votes
    assert MAX_CERT_VOTES == 4096

    # QC claiming cap+1 votes, inside a timeout frame (tag 2): rejected
    # on the count, not after 4097 attempted signature decodes
    bomb = Encoder()
    bomb.raw(Digest.of(b"bomb").to_bytes()).u64(7)
    bomb.u32(MAX_CERT_VOTES + 1)
    with pytest.raises(SerializationError, match="exceeds cap"):
        decode_message(bytes([2]) + bomb.finish())

    # exactly AT the cap the count is legal — the absent vote bytes then
    # die as ordinary truncation, a different failure
    at_cap = Encoder()
    at_cap.raw(Digest.of(b"bomb").to_bytes()).u64(7)
    at_cap.u32(MAX_CERT_VOTES)
    with pytest.raises(SerializationError) as exc:
        decode_message(bytes([2]) + at_cap.finish())
    assert "exceeds cap" not in str(exc.value)

    # TC (tag 3) claiming cap+1 votes: same rejection
    tc_bomb = Encoder().u64(9).u32(MAX_CERT_VOTES + 1)
    with pytest.raises(SerializationError, match="exceeds cap"):
        decode_message(bytes([3]) + tc_bomb.finish())


def test_block_payload_count_bomb_dies_in_the_codec():
    """The payload-count cap was verify-time only (core.py attribution);
    decode-time enforcement stops the forged count from sizing the
    digest-vector read at all."""
    from hotstuff_tpu.consensus.messages import encode_pk
    from hotstuff_tpu.utils.codec import Encoder

    blocks = chain(2)
    b = blocks[-1]
    for count in (MAX_BLOCK_PAYLOADS + 1, 0xFFFFFFFF):
        enc = Encoder().u8(0)  # TAG_PROPOSE
        b.qc.encode(enc)
        enc.flag(False)
        encode_pk(enc, b.author)
        enc.u64(b.round)
        enc.u32(count)
        with pytest.raises(SerializationError, match="exceeds cap"):
            decode_message(enc.finish())


def test_capped_decoder_truncation_sweep():
    """A propose frame carrying a real payload vector: the frame
    decodes whole, every strict prefix dies cleanly, and a count at the
    protocol cap round-trips (the cap rejects forgeries, not the
    protocol's own maximum)."""
    import dataclasses

    blocks = chain(2)
    payloads = tuple(
        Digest.of(bytes([i % 256]) * 8) for i in range(64)
    )
    b = dataclasses.replace(blocks[-1], payloads=payloads)
    frame = encode_propose(b)
    _, decoded = decode_message(frame)
    assert decoded.payloads == payloads

    for cut in range(len(frame)):
        _decode_must_not_crash(frame[:cut])

    full = dataclasses.replace(
        blocks[-1],
        payloads=tuple(
            Digest.of(i.to_bytes(4, "little"))
            for i in range(MAX_BLOCK_PAYLOADS)
        ),
    )
    _, rt = decode_message(encode_propose(full))
    assert len(rt.payloads) == MAX_BLOCK_PAYLOADS


# ---------------------------------------------------------------------------
# compact-certificate corpus (ISSUE 9): the aggregated QC/TC wire form
# is a NEW attack surface — a sentinel vote count, a version byte, one
# aggregate signature and a signer bitmap.  Malformed variants must die
# in the codec (SerializationError) or in verification (ConsensusError),
# never as an unhandled crash, and never be silently accepted.


def _bls_compact_fixture(n: int = 4):
    """(committee, sorted pks, quorum votes, compact QC) over one block
    digest, using small-scalar secrets."""
    from hotstuff_tpu.consensus.config import Committee
    from hotstuff_tpu.consensus.messages import QC, make_signer_bitmap
    from hotstuff_tpu.crypto import PublicKey
    from hotstuff_tpu.crypto.bls import BlsSecretKey, prove_possession
    from hotstuff_tpu.crypto.bls.curve import G1Point

    sks = [BlsSecretKey(i + 2) for i in range(n)]
    by_pk = {PublicKey(sk.public_key().to_bytes()): sk for sk in sks}
    com = Committee.new(
        [
            (pk, 1, ("127.0.0.1", 23_000 + i))
            for i, pk in enumerate(sorted(by_pk))
        ],
        scheme="bls",
        pops={pk: prove_possession(sk).to_bytes() for pk, sk in by_pk.items()},
    )
    pks = com.sorted_keys()
    digest = Digest.of(b"compact fuzz block")
    qc_probe = QC(hash=digest, round=9)
    msg = qc_probe.digest().to_bytes()
    quorum = com.quorum_threshold()
    votes = [
        (pk, Signature(by_pk[pk].sign(msg).to_bytes()))
        for pk in pks[:quorum]
    ]
    agg = G1Point.sum(
        [
            G1Point.from_bytes(sig.to_bytes(), subgroup_check=False)
            for _, sig in votes
        ]
    ).to_bytes()
    qc = QC(
        hash=digest,
        round=9,
        votes=[],
        agg_sig=Signature(agg),
        signers=make_signer_bitmap([pk for pk, _ in votes], pks),
    )
    return com, pks, votes, qc


def test_compact_qc_wire_corpus():
    """Truncations, bitmap/size mismatches, sub-quorum bitmaps and
    garbage aggregates: clean decode errors or verification rejections
    only."""
    from hotstuff_tpu.consensus.errors import (
        ConsensusError,
        QCRequiresQuorum,
    )
    from hotstuff_tpu.consensus.messages import (
        COMPACT_SENTINEL,
        MAX_SIGNER_BITMAP,
        QC,
        make_signer_bitmap,
    )
    from hotstuff_tpu.crypto.scheme import make_cpu_verifier
    from hotstuff_tpu.utils.codec import Encoder

    com, pks, votes, qc = _bls_compact_fixture()
    verifier = make_cpu_verifier("bls")

    # the genuine compact certificate round-trips under the pinned
    # decoder and verifies (inside a timeout frame — QCs never travel
    # bare)
    pk0 = pks[0]
    frame = bytes([2])  # TAG_TIMEOUT
    enc = Encoder()
    qc.encode(enc)
    from hotstuff_tpu.consensus.messages import encode_pk

    enc.u64(9)
    encode_pk(enc, pk0)
    enc.var_bytes(b"\x00" * 48)  # placeholder timeout signature
    frame += enc.finish()
    _, timeout = decode_message(frame, scheme="bls")
    assert timeout.high_qc.is_compact
    assert timeout.high_qc.wire_size() == qc.wire_size()
    timeout.high_qc.verify(com, verifier)  # must not raise

    # 1. truncated bitmap / truncated aggregate: every prefix of the
    #    compact frame dies cleanly in the codec
    for cut in range(len(frame)):
        try:
            decode_message(frame[:cut], scheme="bls")
        except SerializationError:
            pass

    # 2. aggregate-size mismatch: a 64-byte "aggregate" under the BLS
    #    scheme pin (48) is a codec error, not crypto's problem
    wrong = Encoder()
    wrong.raw(qc.hash.to_bytes()).u64(qc.round)
    wrong.u32(COMPACT_SENTINEL).u8(1)
    wrong.var_bytes(b"\x11" * 64)  # ed25519-sized blob
    wrong.var_bytes(qc.signers)
    bad_qc_wire = wrong.finish()
    tc_like = bytes([2]) + bad_qc_wire + frame[1 + qc.wire_size():]
    with pytest.raises(SerializationError):
        decode_message(tc_like, scheme="bls")

    # 3. bitmap above the decode cap dies in the codec
    huge = Encoder()
    huge.raw(qc.hash.to_bytes()).u64(qc.round)
    huge.u32(COMPACT_SENTINEL).u8(1)
    huge.var_bytes(qc.agg_sig.to_bytes())
    huge.var_bytes(b"\xff" * (MAX_SIGNER_BITMAP + 1))
    with pytest.raises(SerializationError):
        decode_message(
            bytes([2]) + huge.finish() + frame[1 + qc.wire_size():],
            scheme="bls",
        )

    # 4. sub-quorum bitmap: decodes fine (structure is legal), fails
    #    check_weight exactly like a sub-quorum vote list
    sub = QC(
        hash=qc.hash,
        round=qc.round,
        votes=[],
        agg_sig=qc.agg_sig,
        signers=make_signer_bitmap([pks[0]], pks),
    )
    with pytest.raises(QCRequiresQuorum):
        sub.check_weight(com)

    # 5. out-of-range signer bit: bit index beyond the committee takes
    #    the UnknownAuthority path in verification, never a crash
    oob = QC(
        hash=qc.hash,
        round=qc.round,
        votes=[],
        agg_sig=qc.agg_sig,
        signers=qc.signers[:-1] + bytes([qc.signers[-1] | 0xF0]),
    )
    with pytest.raises(ConsensusError):
        oob.check_weight(com)

    # 6. garbage aggregate over a valid quorum bitmap: decodes cleanly,
    #    MUST fail verify (the one-pairing check), not decode
    garbage = QC(
        hash=qc.hash,
        round=qc.round,
        votes=[],
        agg_sig=Signature(b"\x99" * 48),
        signers=qc.signers,
    )
    garbage.check_weight(com)  # structurally a quorum
    with pytest.raises(ConsensusError):
        garbage.verify(com, verifier)

    # 7. an ed25519-pinned decoder refuses ANY compact certificate —
    #    the scheme has no aggregate form, so the sentinel itself is
    #    malformed input
    with pytest.raises(SerializationError):
        decode_message(frame, scheme="ed25519")

    # 8. single-byte mutations of the genuine compact frame never crash
    rng = random.Random(0xF026)
    for _ in range(300):
        buf = bytearray(frame)
        buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        try:
            decode_message(bytes(buf), scheme="bls")
        except SerializationError:
            pass


def test_compact_tc_wire_corpus():
    """The compact TC's per-group form: group-count cap, per-group
    bitmap rules, and garbage aggregates failing verify not decode."""
    from hotstuff_tpu.consensus.errors import ConsensusError
    from hotstuff_tpu.consensus.messages import (
        MAX_COMPACT_GROUPS,
        TC,
        make_signer_bitmap,
        timeout_digest,
    )
    from hotstuff_tpu.crypto.bls import BlsSecretKey
    from hotstuff_tpu.crypto.bls.curve import G1Point
    from hotstuff_tpu.crypto.scheme import make_cpu_verifier

    com, pks, _, _ = _bls_compact_fixture()
    verifier = make_cpu_verifier("bls")
    by_pk = {}
    for i in range(len(pks)):
        sk = BlsSecretKey(i + 2)
        from hotstuff_tpu.crypto import PublicKey

        by_pk[PublicKey(sk.public_key().to_bytes())] = sk

    # genuine compact TC: quorum split across two high-qc-round groups
    def group(authors, hq):
        msg = timeout_digest(11, hq).to_bytes()
        sigs = [
            G1Point.from_bytes(
                by_pk[pk].sign(msg).to_bytes(), subgroup_check=False
            )
            for pk in authors
        ]
        return (
            hq,
            Signature(G1Point.sum(sigs).to_bytes()),
            make_signer_bitmap(authors, pks),
        )

    tc = TC(round=11, votes=[], groups=[group(pks[:2], 8), group(pks[2:3], 9)])
    frame = encode_tc(tc)
    _, decoded = decode_message(frame, scheme="bls")
    assert decoded.is_compact
    assert sorted(decoded.high_qc_rounds()) == [8, 8, 9]
    decoded.verify(com, verifier)  # must not raise

    # a node present in TWO groups is authority reuse
    dup = TC(round=11, votes=[], groups=[group(pks[:2], 8), group(pks[1:3], 9)])
    with pytest.raises(ConsensusError):
        dup.verify(com, verifier)

    # garbage aggregate in one group: decodes, fails verify
    g8, g9 = tc.groups
    forged = TC(
        round=11,
        votes=[],
        groups=[g8, (g9[0], Signature(b"\x42" * 48), g9[2])],
    )
    _, fdec = decode_message(encode_tc(forged), scheme="bls")
    with pytest.raises(ConsensusError):
        fdec.verify(com, verifier)

    # group count over the cap dies in the codec
    from hotstuff_tpu.consensus.messages import COMPACT_SENTINEL
    from hotstuff_tpu.utils.codec import Encoder

    enc = Encoder().u8(3)  # TAG_TC
    enc.u64(11).u32(COMPACT_SENTINEL).u8(1)
    enc.u8(MAX_COMPACT_GROUPS + 1)
    with pytest.raises(SerializationError):
        decode_message(enc.finish(), scheme="bls")

    # mutations of the genuine compact TC frame never crash
    rng = random.Random(0xF027)
    for _ in range(300):
        buf = bytearray(frame)
        buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        try:
            decode_message(bytes(buf), scheme="bls")
        except SerializationError:
            pass


# ---------------------------------------------------------------------------
# producer-frame-v2 / ingest-ACK corpus (ISSUE 10): the admission plane
# adds a versioned batched submission frame on the consensus port and a
# typed reply frame on the producer socket — both face unauthenticated
# clients, so the same "clean decode or clean error" property is
# load-bearing.


def _v2_frame(n: int = 5, body_size: int = 48) -> bytes:
    from hotstuff_tpu.consensus.wire import encode_producer_batch

    items = []
    for i in range(n):
        body = bytes([i]) * body_size
        items.append((Digest.of(body), body))
    return encode_producer_batch(items)


def test_producer_v2_round_trip():
    from hotstuff_tpu.consensus.wire import TAG_PRODUCER_V2

    frame = _v2_frame(7)
    tag, payload = decode_message(frame)
    assert tag == TAG_PRODUCER_V2
    assert len(payload) == 7
    for digest, body in payload:
        assert digest == Digest.of(body)
    # item order is preserved — the accepted-prefix admission contract
    # depends on it
    assert [b[0] for _, b in payload] == list(range(7))


def test_producer_v2_batch_bounds():
    from hotstuff_tpu.consensus.wire import (
        MAX_PRODUCER_BATCH,
        encode_producer_batch,
    )

    with pytest.raises(ValueError):
        encode_producer_batch([])
    d = Digest.of(b"x")
    with pytest.raises(ValueError):
        encode_producer_batch([(d, b"")] * (MAX_PRODUCER_BATCH + 1))
    # the cap itself encodes and round-trips
    frame = encode_producer_batch([(d, b"")] * MAX_PRODUCER_BATCH)
    _, payload = decode_message(frame)
    assert len(payload) == MAX_PRODUCER_BATCH


def test_producer_v2_wire_corpus():
    """Truncations, bad version byte, oversized declared count, and
    single-byte mutations: SerializationError or clean decode only."""
    from hotstuff_tpu.consensus.wire import (
        MAX_PRODUCER_BATCH,
        PRODUCER_FRAME_VERSION,
        TAG_PRODUCER_V2,
    )

    frame = _v2_frame(5)
    decode_message(frame)  # sanity: the original decodes

    # every truncation dies cleanly
    for cut in range(len(frame)):
        _decode_must_not_crash(frame[:cut])
    _decode_must_not_crash(frame + b"\x00")  # trailing junk

    # any version byte except the pinned one is malformed input
    for version in range(256):
        if version == PRODUCER_FRAME_VERSION:
            continue
        mutated = bytes([frame[0], version]) + frame[2:]
        with pytest.raises(SerializationError):
            decode_message(mutated)

    # declared count of 0 and counts past the batch cap die in the
    # codec, never as an allocation attempt
    import struct

    head = bytes([TAG_PRODUCER_V2, PRODUCER_FRAME_VERSION])
    for count in (0, MAX_PRODUCER_BATCH + 1, 0xFFFFFFFF):
        with pytest.raises(SerializationError):
            decode_message(head + struct.pack("<I", count))

    # a count larger than the items actually present dies cleanly
    inflated = head + struct.pack("<I", 9) + frame[6:]
    with pytest.raises(SerializationError):
        decode_message(inflated)

    rng = random.Random(0xF028)
    for _ in range(400):
        buf = bytearray(frame)
        buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        _decode_must_not_crash(bytes(buf))


def test_ingest_ack_round_trip_and_corpus():
    from hotstuff_tpu.consensus.wire import (
        INGEST_ACK_TAG,
        INGEST_BUSY,
        INGEST_OK,
        decode_ingest_ack,
        encode_ingest_ack,
    )

    # OK form: nothing shed, no retry hint
    ok = decode_ingest_ack(encode_ingest_ack(12, 0, 640, 0))
    assert ok is not None and not ok.busy and ok.status == INGEST_OK
    assert (ok.accepted, ok.shed, ok.credit, ok.retry_after_ms) == (
        12, 0, 640, 0,
    )
    # BUSY form: a nonzero shed flips the status
    busy = decode_ingest_ack(encode_ingest_ack(3, 9, 0, 250))
    assert busy is not None and busy.busy and busy.status == INGEST_BUSY
    assert (busy.accepted, busy.shed) == (3, 9)
    # encode clamps instead of wrapping
    big = decode_ingest_ack(encode_ingest_ack(1 << 40, -5, 0, 1 << 40))
    assert big.accepted == (1 << 32) - 1 and big.shed == 0

    # non-ACK frames are None, not errors: the legacy reply and
    # anything else that doesn't lead with the ACK tag
    assert decode_ingest_ack(b"Ack") is None
    assert decode_ingest_ack(b"") is None
    assert decode_ingest_ack(b"\x00\x01\x02") is None

    frame = encode_ingest_ack(3, 9, 64, 250)
    # bad version / bad status are malformed, not silently decoded
    with pytest.raises(SerializationError):
        decode_ingest_ack(bytes([INGEST_ACK_TAG, 99]) + frame[2:])
    with pytest.raises(SerializationError):
        decode_ingest_ack(frame[:2] + bytes([7]) + frame[3:])
    # truncations and trailing junk die cleanly
    for cut in range(1, len(frame)):
        with pytest.raises(SerializationError):
            decode_ingest_ack(frame[:cut])
    with pytest.raises(SerializationError):
        decode_ingest_ack(frame + b"\x00")

    # mutations: typed ACK, None, or SerializationError — never a crash
    rng = random.Random(0xF029)
    for _ in range(400):
        buf = bytearray(frame)
        buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        try:
            decode_ingest_ack(bytes(buf))
        except SerializationError:
            pass


# ---------------------------------------------------------------------------
# reconfiguration corpus (ISSUE 14): the epoch-change submission frame
# (TAG_RECONFIG) arrives on the unauthenticated consensus port, and the
# state-sync manifest v2 carries attacker-relayable certified schedule
# links — both get the same decode-time-cap treatment the wire-decoder-
# bounds lint demands: forged counts and sizes die on the count, never
# as an allocation or a crash.


def _reconfig_frame():
    from hotstuff_tpu.consensus.config import Committee
    from hotstuff_tpu.consensus.reconfig import ReconfigOp
    from hotstuff_tpu.consensus.wire import encode_reconfig

    pairs = keys()
    new = Committee.new(
        [
            (pk, 1, ("127.0.0.1", 24_000 + i))
            for i, (pk, _) in enumerate(pairs)
        ],
        epoch=2,
    )
    sponsor_pk, sponsor_sk = pairs[0]
    op = ReconfigOp(new_committee=new, margin=8, sponsor=sponsor_pk)
    op.signature = Signature.new(Digest(op.digest()), sponsor_sk)
    return encode_reconfig(op), op


def test_reconfig_frame_round_trip():
    from hotstuff_tpu.consensus.wire import TAG_RECONFIG

    frame, op = _reconfig_frame()
    tag, decoded = decode_message(frame)
    assert tag == TAG_RECONFIG
    assert decoded.margin == op.margin
    assert decoded.sponsor == op.sponsor
    assert decoded.signature == op.signature
    assert decoded.new_committee.epoch == 2
    assert decoded.digest() == op.digest()
    # the ed25519-pinned decoder accepts it too (all keys are ed25519)
    decode_message(frame, scheme="ed25519")


def test_reconfig_truncation_sweep():
    frame, _ = _reconfig_frame()
    decode_message(frame)  # sanity: the original decodes
    for cut in range(len(frame)):
        _decode_must_not_crash(frame[:cut])
    _decode_must_not_crash(frame + b"\x00")  # trailing junk
    _decode_must_not_crash(frame + frame)


def test_reconfig_bad_version_bytes():
    from hotstuff_tpu.consensus.reconfig import RECONFIG_OP_VERSION

    frame, _ = _reconfig_frame()
    # the op version byte sits right after the tag
    for version in range(256):
        if version == RECONFIG_OP_VERSION:
            continue
        with pytest.raises(SerializationError, match="version"):
            decode_message(bytes([frame[0], version]) + frame[2:])


def test_reconfig_member_count_bomb_dies_in_the_codec():
    from hotstuff_tpu.consensus.reconfig import (
        MAX_RECONFIG_MEMBERS,
        RECONFIG_OP_VERSION,
    )
    from hotstuff_tpu.consensus.wire import TAG_RECONFIG
    from hotstuff_tpu.utils.codec import Encoder

    # a forged count past the cap is rejected on the count itself,
    # before the first member decode
    bomb = Encoder().u8(TAG_RECONFIG).u8(RECONFIG_OP_VERSION)
    bomb.u64(2).var_bytes(b"ed25519").u16(MAX_RECONFIG_MEMBERS + 1)
    with pytest.raises(SerializationError, match="exceeds cap"):
        decode_message(bomb.finish())

    # exactly AT the cap the count is legal — the absent member bytes
    # then die as ordinary truncation, a different failure
    at_cap = Encoder().u8(TAG_RECONFIG).u8(RECONFIG_OP_VERSION)
    at_cap.u64(2).var_bytes(b"ed25519").u16(MAX_RECONFIG_MEMBERS)
    with pytest.raises(SerializationError) as exc:
        decode_message(at_cap.finish())
    assert "exceeds cap" not in str(exc.value)

    # oversized per-member fields (scheme, host, key) die on their own
    # var_bytes caps
    fat_scheme = Encoder().u8(TAG_RECONFIG).u8(RECONFIG_OP_VERSION)
    fat_scheme.u64(2).var_bytes(b"x" * 64)
    with pytest.raises(SerializationError):
        decode_message(fat_scheme.finish())


def test_reconfig_mutation_storm():
    rng = random.Random(0xF030)
    frame, _ = _reconfig_frame()
    for _ in range(500):
        buf = bytearray(frame)
        buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        _decode_must_not_crash(bytes(buf))
    # multi-byte storms too: up to 8 flips per frame
    for _ in range(200):
        buf = bytearray(frame)
        for _ in range(rng.randrange(2, 9)):
            buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        _decode_must_not_crash(bytes(buf))


def test_manifest_schedule_links_corpus():
    """Manifest v2's certified schedule links: round trip, the link-count
    cap, and the per-link byte cap — all enforced at decode time."""
    import struct

    from hotstuff_tpu.consensus.wire import (
        MAX_SCHEDULE_LINKS,
        TAG_STATE_MANIFEST,
        encode_state_manifest,
    )

    pk = keys()[0][0]
    qc = qc_for_block(chain(1)[0])
    links = [(b"block-bytes-%d" % i, b"qc-bytes-%d" % i) for i in range(3)]
    frame = encode_state_manifest(
        7, b"\x11" * 32, 42, 100, 2, 0, qc, pk, links=links
    )
    tag, manifest = decode_message(frame)
    assert tag == TAG_STATE_MANIFEST
    assert manifest.links == tuple(links)

    # the encoder refuses an over-cap link list outright
    with pytest.raises(ValueError, match="schedule links"):
        encode_state_manifest(
            7, b"\x11" * 32, 42, 100, 2, 0, qc, pk,
            links=[(b"b", b"q")] * (MAX_SCHEDULE_LINKS + 1),
        )

    # a forged on-wire count dies on the count (the u16 sits where the
    # empty-list frame ends)
    empty = encode_state_manifest(7, b"\x11" * 32, 42, 100, 2, 0, qc, pk)
    forged = empty[:-2] + struct.pack("<H", MAX_SCHEDULE_LINKS + 1)
    with pytest.raises(SerializationError, match="exceeds cap"):
        decode_message(forged)

    # a link element past the byte cap dies in var_bytes, not as an
    # allocation of attacker-chosen size
    from hotstuff_tpu.consensus.wire import MAX_SCHEDULE_LINK_BYTES

    fat = encode_state_manifest(
        7, b"\x11" * 32, 42, 100, 2, 0, qc, pk,
        links=[(b"\x00" * (MAX_SCHEDULE_LINK_BYTES + 1), b"q")],
    )
    with pytest.raises(SerializationError):
        decode_message(fat)

    # truncation sweep over the linked manifest (stride keeps it fast)
    for cut in range(0, len(frame), max(1, len(frame) // 60)):
        _decode_must_not_crash(frame[:cut])

    # mutations never crash
    rng = random.Random(0xF031)
    for _ in range(300):
        buf = bytearray(frame)
        buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        _decode_must_not_crash(bytes(buf))

# ---------------------------------------------------------------------------
# zero-copy ingest differential harness (ISSUE 20): the native frame
# parser (native/wave_pack.cpp) and the Python Decoder must accept /
# reject BYTE-IDENTICAL corpora — a frame only the native side accepts
# would be mis-ingested past the codec's caps, and a frame only Python
# accepts would silently lose the fast path.  Every test here drives
# the SAME byte corpus through both and asserts zero divergence; the
# suite skips cleanly where the native toolchain is absent.


def _wave_native():
    from hotstuff_tpu.crypto import native_ed25519 as ne

    if not ne.wave_pack_available():
        pytest.skip("native wave packer unavailable")
    return ne


def _py_accepts_vote(frame: bytes) -> bool:
    from hotstuff_tpu.consensus.wire import TAG_VOTE

    try:
        tag, _ = decode_message(frame, scheme="ed25519")
    except SerializationError:
        return False
    return tag == TAG_VOTE


def _py_producer_items(frame: bytes):
    from hotstuff_tpu.consensus.wire import TAG_PRODUCER_V2

    try:
        tag, payload = decode_message(frame, scheme="ed25519")
    except SerializationError:
        return None
    if tag != TAG_PRODUCER_V2:
        return None
    return payload


def _raw_vote_frame(rng):
    """A wire-shaped ed25519 vote frame with random contents (decode
    never verifies signatures, so random bytes exercise the codec the
    same way real votes do) and the claim tuple ``Vote.claim()`` would
    produce for it."""
    import struct

    h = rng.randbytes(32)
    rnd = rng.randrange(1 << 63)
    pk = rng.randbytes(32)
    sig = rng.randbytes(64)
    frame = (
        bytes([1]) + h + struct.pack("<Q", rnd)
        + struct.pack("<I", 32) + pk
        + struct.pack("<I", 64) + sig
    )
    claim = (
        "one",
        Digest.of(h + struct.pack("<Q", rnd)).to_bytes(),
        pk,
        sig,
    )
    return frame, claim


def test_ingest_tag_constants_match_wire():
    """The receiver/service ingest taps hardcode wire tags (importing
    consensus.wire there would cycle) — pin them to the live values."""
    from hotstuff_tpu.consensus.wire import TAG_PRODUCER_V2, TAG_VOTE
    from hotstuff_tpu.crypto.async_service import INGEST_TAG_VOTE
    from hotstuff_tpu.network import receiver

    assert INGEST_TAG_VOTE == TAG_VOTE
    assert receiver._TAG_VOTE == TAG_VOTE
    assert receiver._TAG_PRODUCER_V2 == TAG_PRODUCER_V2


def test_native_vote_probe_matches_decoder():
    """Accept/reject parity on the vote corpus: real signed votes,
    every truncation, trailing junk, length-field bombs, and a
    mutation storm — zero divergence allowed."""
    import struct

    ne = _wave_native()
    rng = random.Random(0xF040)

    def check(frame: bytes):
        assert ne.probe_vote(frame) == _py_accepts_vote(frame), frame.hex()

    # a REAL signed vote (and the decoder sanity-checks it first)
    blocks = chain(3)
    pk, sk = keys()[0]
    real = encode_vote(signed_vote(blocks[1], pk, sk))
    assert _py_accepts_vote(real) and ne.probe_vote(real)

    # synthetic well-formed frames
    frames = [real] + [_raw_vote_frame(rng)[0] for _ in range(20)]
    for frame in frames[:4]:
        for cut in range(len(frame) + 1):
            check(frame[:cut])
        check(frame + b"\x00")
        check(frame + frame)
    # forged pk/sig length prefixes around the fixed sizes
    base = bytearray(frames[1])
    for off in (41, 77):
        for val in (0, 1, 31, 33, 48, 63, 65, 96, 1 << 16, 0xFFFFFFFF):
            buf = bytearray(base)
            buf[off : off + 4] = struct.pack("<I", val)
            check(bytes(buf))
    # mutation storm: single- and multi-byte flips
    for frame in frames:
        for _ in range(200):
            buf = bytearray(frame)
            for _ in range(rng.randrange(1, 4)):
                buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
            check(bytes(buf))
    # random tag-1-prefixed garbage of assorted lengths
    for _ in range(500):
        check(b"\x01" + rng.randbytes(rng.randrange(0, 200)))


def test_native_pack_digest_matches_vote_claim():
    """The digest the native packer computes (single-block SHA-512 over
    hash||round) must equal ``Vote.claim()``'s — it becomes the claim
    key the arena adoption matches against."""
    ne = _wave_native()
    from hotstuff_tpu.crypto.async_service import make_pad_claim

    pad = make_pad_claim()
    packer = ne.WavePacker(16, 2)
    try:
        assert packer.set_pad(pad[1], pad[2], pad[3])
        blocks = chain(3)
        for i, (pk, sk) in enumerate(keys()[:3]):
            vote = signed_vote(blocks[1], pk, sk)
            res = packer.pack_vote(encode_vote(vote))
            assert not isinstance(res, int), res
            slot, digest = res
            assert slot == i
            assert digest == vote.claim()[1]
    finally:
        packer.close()


def test_native_producer_parse_matches_decoder():
    """Producer-v2 parity: on every corpus frame the native parser and
    the Python Decoder agree on accept/reject, and on acceptance the
    digest column and body spans reproduce the decoded items exactly."""
    import struct

    ne = _wave_native()
    from hotstuff_tpu.consensus.wire import (
        MAX_PRODUCER_BATCH,
        PRODUCER_FRAME_VERSION,
        TAG_PRODUCER_V2,
    )

    assert ne.MAX_PRODUCER_BATCH == MAX_PRODUCER_BATCH

    def check(frame: bytes):
        native = ne.parse_producer(frame)
        items = _py_producer_items(frame)
        if items is None:
            assert native is None, frame[:32].hex()
            return
        assert native is not None, frame[:32].hex()
        digests, spans = native
        assert len(spans) == len(items)
        for i, (digest, body) in enumerate(items):
            assert digests[i * 32 : (i + 1) * 32] == digest.to_bytes()
            off, ln = spans[i]
            assert frame[off : off + ln] == body

    rng = random.Random(0xF041)
    frames = [
        _v2_frame(1, body_size=0),
        _v2_frame(5),
        _v2_frame(16, body_size=1),
        _v2_frame(3, body_size=300),
    ]
    for frame in frames:
        check(frame)
        for cut in range(len(frame) + 1):
            check(frame[:cut])
        check(frame + b"\x00")
    # version bytes and count bombs
    frame = frames[1]
    for version in (0, 1, 3, 255):
        check(bytes([frame[0], version]) + frame[2:])
    head = bytes([TAG_PRODUCER_V2, PRODUCER_FRAME_VERSION])
    for count in (0, 1, MAX_PRODUCER_BATCH, MAX_PRODUCER_BATCH + 1,
                  0xFFFFFFFF):
        check(head + struct.pack("<I", count))
        check(head + struct.pack("<I", count) + frame[6:])
    # per-item length bombs around the body cap
    for ln in (0, 1, 65536, 65537, 0xFFFFFFFF):
        bomb = head + struct.pack("<I", 1) + b"\xaa" * 32
        bomb += struct.pack("<I", ln) + b"\xbb" * min(ln, 70_000)
        check(bomb)
    # mutation storm
    for frame in frames:
        for _ in range(300):
            buf = bytearray(frame)
            for _ in range(rng.randrange(1, 4)):
                buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
            check(bytes(buf))
    # random tag-6-prefixed garbage
    for _ in range(500):
        check(bytes([TAG_PRODUCER_V2]) + rng.randbytes(rng.randrange(0, 300)))


def test_flatten_claims_vs_arena_columns_every_bucket():
    """Column parity at every wave bucket: the adopted arena's
    digest/pk/sig columns must be byte-identical to what
    ``flatten_claims`` produces for the same claims, with pad rows
    equal to the shared pad claim — the property that makes arena
    adoption a drop-in replacement for the flatten/prepare hop."""
    np = pytest.importorskip("numpy")
    _wave_native()
    from hotstuff_tpu.crypto.async_service import (
        DEFAULT_WAVE_BUCKETS,
        ZeroCopyIngest,
        flatten_claims,
        make_pad_claim,
    )

    rng = random.Random(0xF042)
    pad = make_pad_claim()
    ing = ZeroCopyIngest(capacity=DEFAULT_WAVE_BUCKETS[-1], ring_depth=3)
    for bucket in DEFAULT_WAVE_BUCKETS:
        for n in (bucket, max(1, bucket - 3)):
            pairs = [_raw_vote_frame(rng) for _ in range(n)]
            for frame, _ in pairs:
                assert ing.note_vote_frame(frame)
            claims = [c for _, c in pairs]
            wave = ing.try_adopt(claims, DEFAULT_WAVE_BUCKETS)
            assert wave is not None, (bucket, n)
            assert wave.n == n and wave.rows == bucket
            digests, pks, sigs, spans = flatten_claims(claims)
            assert spans == [(i, i + 1) for i in range(n)]
            dig_v = np.frombuffer(wave.dig, np.uint8).reshape(bucket, 32)
            pk_v = np.frombuffer(wave.pk, np.uint8).reshape(bucket, 32)
            sig_v = np.frombuffer(wave.sig, np.uint8).reshape(bucket, 64)
            for i in range(n):
                assert dig_v[i].tobytes() == digests[i]
                assert pk_v[i].tobytes() == pks[i]
                assert sig_v[i].tobytes() == sigs[i]
            for i in range(n, bucket):
                assert dig_v[i].tobytes() == pad[1]
                assert pk_v[i].tobytes() == pad[2]
                assert sig_v[i].tobytes() == pad[3]
            wave.release()
    counters = ing.counters()
    assert counters["zero_copy_waves"] == 2 * len(DEFAULT_WAVE_BUCKETS)
    assert counters["fallback_waves"] == 0
