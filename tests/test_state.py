"""Replicated execution layer unit tests: typed-op codec, incremental
state root, determinism across replicas, meta persistence, snapshot
manifest/chunk/adopt roundtrips, delta filtering, and the state wire
frames (request/manifest/chunk/read/value).

The e2e half (SIGKILL + snapshot rejoin with converging roots) lives in
tests/test_crash_rejoin_e2e.py; these tests pin the building blocks it
relies on.
"""

from __future__ import annotations

import collections
import os
import random
import struct

import pytest

from hotstuff_tpu.consensus.errors import SerializationError
from hotstuff_tpu.consensus.wire import (
    MAX_STATE_CHUNK_ENTRIES,
    STATE_REQ_CHUNK,
    STATE_REQ_DELTA,
    STATE_REQ_MANIFEST,
    STATE_READ_LEDGER,
    STATE_READ_USER,
    STATE_VALUE_TAG,
    TAG_STATE_CHUNK,
    TAG_STATE_MANIFEST,
    TAG_STATE_READ,
    TAG_STATE_REQUEST,
    decode_message,
    decode_state_value,
    encode_state_chunk,
    encode_state_manifest,
    encode_state_read,
    encode_state_request,
    encode_state_value,
)
from hotstuff_tpu.consensus.messages import QC, Block
from hotstuff_tpu.crypto import Digest
from hotstuff_tpu.store import Store
from hotstuff_tpu.store.state import (
    GENESIS_ROOT,
    MAX_OPS_PER_BODY,
    OP_BODY_OFFSET,
    OP_MAGIC,
    SNAPSHOT_CHUNK_ENTRIES,
    SnapshotManifest,
    StateError,
    StateMachine,
    decode_ops,
    encode_ops,
    fold_root,
)

from .common import chain, keys, qc_for_block


def _store(tmp_path, name: str) -> Store:
    return Store(str(tmp_path / name))


def _typed_body(ops) -> bytes:
    """A payload body as the ingest plane stores it: the 8-byte producer
    counter prefix, then the typed-op blob."""
    return b"\x00" * OP_BODY_OFFSET + encode_ops(ops)


# ---- typed-op codec --------------------------------------------------------


def test_ops_codec_roundtrip():
    ops = [
        ("put", b"alpha", b"1"),
        ("del", b"beta"),
        ("put", b"gamma", b""),
        ("put", b"k" * 256, b"v" * 4096),
    ]
    body = _typed_body(ops)
    assert decode_ops(body) == ops
    assert decode_ops(_typed_body([])) == []


def test_decode_ops_rejects_malformed():
    # opaque (non-typed) bodies are legal and decode to None
    assert decode_ops(b"\x00" * OP_BODY_OFFSET + b"not-typed") is None
    assert decode_ops(b"") is None

    good = _typed_body([("put", b"key", b"value")])
    # truncation anywhere inside the op must yield None, never raise
    for cut in range(OP_BODY_OFFSET + len(OP_MAGIC) + 1, len(good)):
        assert decode_ops(good[:cut]) is None

    prefix = b"\x00" * OP_BODY_OFFSET + OP_MAGIC
    # zero-length key
    assert decode_ops(prefix + bytes([0, 0, 0, 0, 0, 0, 0])) is None
    # unknown op kind
    assert decode_ops(prefix + bytes([7, 1, 0, 0, 0, 0, 0]) + b"k") is None
    # delete carrying a value length
    assert decode_ops(prefix + bytes([1, 1, 0, 1, 0, 0, 0]) + b"k") is None
    # op-count bomb
    too_many = _typed_body(
        [("put", b"k", b"v")] * (MAX_OPS_PER_BODY + 1)
    )
    assert decode_ops(too_many) is None
    # exactly at the cap is fine
    at_cap = _typed_body([("put", b"k", b"v")] * MAX_OPS_PER_BODY)
    assert len(decode_ops(at_cap)) == MAX_OPS_PER_BODY


def test_fold_root_accepts_bytes_and_digest():
    d = Digest.random()
    block = Digest.random().to_bytes()
    via_digest = fold_root(GENESIS_ROOT, 7, block, [d])
    via_bytes = fold_root(GENESIS_ROOT, 7, block, [d.to_bytes()])
    assert via_digest == via_bytes
    assert via_digest != GENESIS_ROOT
    # the fold is order- and round-sensitive
    assert fold_root(GENESIS_ROOT, 8, block, [d]) != via_digest


# ---- deterministic apply ---------------------------------------------------


def test_apply_is_deterministic_across_replicas(tmp_path):
    blocks = chain(5)
    sm_a = StateMachine(_store(tmp_path, "a"))
    sm_b = StateMachine(_store(tmp_path, "b"))
    for block in blocks:
        root_a = sm_a.apply_block(block)
        root_b = sm_b.apply_block(block)
        assert root_a == root_b
    assert sm_a.version == sm_b.version == len(blocks)
    assert sm_a.root == sm_b.root
    assert sm_a.reported_root == sm_a.root
    assert sm_a.last_round == blocks[-1].round


def test_reported_root_diverges_under_shadow_digest(tmp_path):
    blocks = chain(3)
    honest = StateMachine(_store(tmp_path, "honest"))
    collude = StateMachine(_store(tmp_path, "collude"))
    for block in blocks[:-1]:
        honest.apply_block(block)
        collude.apply_block(block)
    honest.apply_block(blocks[-1])
    collude.apply_block(blocks[-1], reported_digest=Digest.random())
    # the lie shows up in the claimed root, never in the real state
    assert collude.root == honest.root
    assert collude.reported_root != honest.reported_root


def test_apply_skips_already_applied_rounds(tmp_path):
    blocks = chain(2)
    sm = StateMachine(_store(tmp_path, "db"))
    assert sm.apply_block(blocks[0]) is not None
    before = (sm.version, sm.root, sm.applied_payloads)
    # crash-recovery overlap: the consensus cursor can trail state
    assert sm.apply_block(blocks[0]) is None
    assert (sm.version, sm.root, sm.applied_payloads) == before
    assert sm.apply_block(blocks[1]) is not None
    assert sm.version == 2


def test_meta_persists_across_reopen(tmp_path):
    store = _store(tmp_path, "db")
    sm = StateMachine(store)
    for block in chain(4):
        sm.apply_block(block)
    anchor = sm.anchor()
    reported = sm.reported_root
    store.engine.close()

    sm2 = StateMachine(_store(tmp_path, "db"))
    assert sm2.anchor() == anchor
    assert sm2.reported_root == reported
    assert sm2.applied_payloads == sm.applied_payloads


# ---- one append a block: the batched apply against a plain reference ------


def _random_blocks(rng, store, count: int):
    """Blocks of 0 to 30 payloads.  Some payloads' bodies are in the
    store, as the ingest plane leaves them at a payload's home: typed
    (puts and deletes over a small key space, so one block writes a key
    several times, a put and a delete of one key in one body included),
    opaque, or malformed."""
    (author, _), = keys()[:1]
    blocks, round_ = [], 0
    user_keys = [b"k%d" % i for i in range(6)]
    for _ in range(count):
        round_ += rng.randrange(1, 4)
        payloads = [
            Digest(bytes(rng.randrange(256) for _ in range(32)))
            for _ in range(rng.randrange(0, 31))
        ]
        if payloads and rng.random() < 0.3:
            payloads.append(payloads[0])  # one digest twice in a block
        for digest in payloads:
            draw = rng.random()
            if draw < 0.25:
                ops = []
                for _ in range(rng.randrange(0, 5)):
                    key = rng.choice(user_keys)
                    if rng.random() < 0.6:
                        ops.append(("put", key, b"v%d" % rng.randrange(1000)))
                    else:
                        ops.append(("del", key))
                if rng.random() < 0.5:
                    ops += [("put", b"both", b"x"), ("del", b"both")]
                body = _typed_body(ops)
            elif draw < 0.35:
                body = b"\x00" * OP_BODY_OFFSET + b"opaque-body"
            elif draw < 0.40:
                body = _typed_body([("put", b"cut", b"short")])[:-3]
            else:
                continue
            store.engine.put(b"p" + digest.to_bytes(), body)
        blocks.append(Block(qc=QC.genesis(), tc=None, author=author,
                            round=round_, payloads=tuple(payloads)))
    return blocks


class _Reference:
    """The execution layer as it was written record by record: a dict
    for the engine, one put a ledger entry, a typed operation and the
    meta cursor, in that order."""

    def __init__(self, bodies: dict):
        self.kv = dict(bodies)
        self.version = self.last_round = self.applied = 0
        self.root = self.reported = GENESIS_ROOT

    def apply(self, block, reported_digest=None):
        if block.round <= self.last_round:
            return None
        for seq, digest in enumerate(block.payloads):
            raw = digest.to_bytes()
            self.kv[b"s/l" + raw] = struct.pack("<QI", block.round, seq)
            self.applied += 1
            body = self.kv.get(b"p" + raw)
            for op in (decode_ops(body) or ()) if body is not None else ():
                alive = op[0] == "put"
                self.kv[b"s/u" + op[1]] = struct.pack(
                    "<QB", block.round, alive
                ) + (op[2] if alive else b"")
        self.version += 1
        self.last_round = block.round
        real = block.digest().to_bytes()
        self.root = fold_root(self.root, block.round, real, block.payloads)
        shadow = real if reported_digest is None else reported_digest.to_bytes()
        self.reported = fold_root(
            self.reported, block.round, shadow, block.payloads
        )
        self.kv[b"s/meta"] = struct.pack(
            "<QQ32sQ", self.version, self.last_round, self.root, self.applied
        ) + self.reported
        return self.reported


def _state_of(engine) -> dict:
    return {k: engine.get(k) for k in engine.keys()}


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_batched_apply_equals_the_per_record_reference(
    tmp_path, engine_cls, seed
):
    rng = random.Random(seed)
    path = str(tmp_path / "db")
    store = Store(path, engine=engine_cls(path))
    blocks = _random_blocks(rng, store, 25)
    ref = _Reference(_state_of(store.engine))
    sm = StateMachine(store)
    for i, block in enumerate(blocks):
        shadow = Digest.random() if i % 7 == 3 else None
        assert sm.apply_block(block, shadow) == ref.apply(block, shadow)
        if i % 5 == 0:  # the recovery overlap: an applied round again
            assert sm.apply_block(block) is None and ref.apply(block) is None
        assert (sm.root, sm.reported_root) == (ref.root, ref.reported)
    assert _state_of(store.engine) == ref.kv  # meta, ledger, user-KV, bodies
    assert (sm.version, sm.last_round, sm.applied_payloads) == (
        ref.version, ref.last_round, ref.applied
    )
    assert sm.reported_root != sm.root  # a shadow digest was reported
    for key in [b"k%d" % i for i in range(6)] + [b"both", b"cut"]:
        raw = ref.kv.get(b"s/u" + key)
        alive = raw is not None and raw[8] == 1
        expect = (int.from_bytes(raw[:8], "little"), raw[9:]) if alive else None
        assert sm.read_user(key) == expect
    assert sm.read_user(b"both") is None  # put then deleted in one body
    snapshot = [e for i in range(sm.manifest().chunk_count) for e in sm.chunk(i)]
    assert snapshot == sorted(
        (k, v) for k, v in ref.kv.items()
        if k.startswith(b"s/") and k != b"s/meta"
    )
    store.close()
    # and the log holds it all: a reopened node is where this one was
    reopened = StateMachine(Store(path, engine=engine_cls(path)))
    assert reopened.anchor() == sm.anchor()
    assert reopened.reported_root == sm.reported_root
    assert _state_of(reopened.store.engine) == ref.kv
    reopened.store.close()


class _CountingEngine:
    """Counts the calls that cross into an engine."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = collections.Counter()

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if not callable(attr):
            return attr

        def counted(*args):
            self.calls[name] += 1
            return attr(*args)

        return counted


def test_apply_is_exactly_one_append_and_two_crossings_a_block(
    tmp_path, engine_cls
):
    from hotstuff_tpu.store.engine import WAL_COUNTS

    path = str(tmp_path / "db")
    engine = _CountingEngine(engine_cls(path))
    store = Store(path, engine=engine)
    blocks = _random_blocks(random.Random(9), store, 12)
    sm = StateMachine(store)
    engine.calls.clear()
    appends, records = WAL_COUNTS.appends, WAL_COUNTS.records
    for block in blocks:
        sm.apply_block(block)
    assert engine.calls == {"put_many": len(blocks), "get_many": len(blocks)}
    assert WAL_COUNTS.appends - appends == len(blocks)
    assert WAL_COUNTS.records - records == (
        sm.applied_payloads + sm.typed_ops + len(blocks)
    )
    store.close()


def test_wal_torn_inside_an_apply_reopens_at_the_previous_round(
    tmp_path, engine_cls
):
    """Whatever prefix of a block's one append a crash leaves, the node
    reopens at the round before it (the meta cursor is the batch's last
    record) and applying the block again lands on the same root and
    the same state."""
    rng = random.Random(5)
    path = str(tmp_path / "db")
    store = Store(path, engine=engine_cls(path))
    blocks = [b for b in _random_blocks(rng, store, 8) if b.payloads]
    *before, torn = blocks
    sm = StateMachine(store)
    for block in before:
        sm.apply_block(block)
    anchor, reported = sm.anchor(), sm.reported_root
    store.engine.close()
    wal = os.path.join(path, "wal.log")
    with open(wal, "rb") as f:
        head = f.read()
    store = Store(path, engine=engine_cls(path))
    sm = StateMachine(store)
    final_reported = sm.apply_block(torn)
    final_anchor, final_state = sm.anchor(), _state_of(store.engine)
    store.engine.close()
    with open(wal, "rb") as f:
        full = f.read()
    assert full[: len(head)] == head and len(full) > len(head) + 100
    cuts = sorted(
        set(range(len(head), len(full), 7))
        | set(range(len(head), len(head) + 60))
        | set(range(len(full) - 130, len(full)))
    )
    for cut in cuts:
        again = str(tmp_path / ("db-%d" % cut))
        os.makedirs(again)
        with open(os.path.join(again, "wal.log"), "wb") as f:
            f.write(full[:cut])
        store = Store(again, engine=engine_cls(again))
        sm = StateMachine(store)
        assert sm.anchor() == anchor and sm.reported_root == reported, cut
        assert sm.apply_block(before[-1]) is None
        assert sm.apply_block(torn) == final_reported
        assert sm.anchor() == final_anchor
        assert _state_of(store.engine) == final_state, cut
        store.close()
    # the whole append in the log: the node reopens past the block
    store = Store(path, engine=engine_cls(path))
    sm = StateMachine(store)
    assert sm.anchor() == final_anchor and sm.apply_block(torn) is None
    store.close()


def test_adopt_writes_the_snapshot_and_its_cursor_as_one_append(tmp_path):
    from hotstuff_tpu.store.engine import WAL_COUNTS

    src = StateMachine(_store(tmp_path, "src"))
    for block in chain(5):
        src.apply_block(block)
    entries = [e for i in range(src.manifest().chunk_count) for e in src.chunk(i)]
    dst_store = _store(tmp_path, "dst")
    dst = StateMachine(dst_store)
    appends = WAL_COUNTS.appends
    dst.adopt(src.manifest(), entries)
    assert WAL_COUNTS.appends - appends == 1
    # a poisoned snapshot writes nothing at all
    with pytest.raises(StateError):
        dst.adopt(src.manifest(), entries + [(b"p" + b"\x00" * 32, b"body")])
    assert WAL_COUNTS.appends - appends == 1
    dst_store.engine.close()
    reopened = StateMachine(_store(tmp_path, "dst"))
    assert reopened.anchor() == src.anchor()
    assert reopened._entries() == entries


# ---- typed ops and the read path -------------------------------------------


def test_typed_ops_materialize_user_state(tmp_path):
    store = _store(tmp_path, "db")
    blocks = chain(3)
    # stash typed bodies for the first two blocks' payloads, as the
    # ingest plane would have before commit
    body0 = _typed_body([("put", b"user", b"v1")])
    body1 = _typed_body([("put", b"user", b"v2"), ("del", b"gone")])
    store.engine.put(b"p" + blocks[0].payloads[0].to_bytes(), body0)
    store.engine.put(b"p" + blocks[1].payloads[0].to_bytes(), body1)

    sm = StateMachine(store)
    for block in blocks:
        sm.apply_block(block)

    round_, value = sm.read_user(b"user")
    assert value == b"v2"
    assert round_ == blocks[1].round
    # tombstone and never-written keys both read as absent
    assert sm.read_user(b"gone") is None
    assert sm.read_user(b"never") is None
    assert sm.typed_ops == 3

    # every committed payload is in the ledger index
    for block in blocks:
        entry = sm.read_ledger(block.payloads[0].to_bytes())
        assert entry == (block.round, 0)
    assert sm.read_ledger(Digest.random().to_bytes()) is None


# ---- snapshots -------------------------------------------------------------


def test_snapshot_roundtrip_into_fresh_store(tmp_path):
    src_store = _store(tmp_path, "src")
    blocks = chain(6)
    src_store.engine.put(
        b"p" + blocks[2].payloads[0].to_bytes(),
        _typed_body([("put", b"carried", b"over")]),
    )
    src = StateMachine(src_store)
    for block in blocks:
        src.apply_block(block)

    manifest = src.manifest()
    assert manifest.version == src.version
    assert manifest.root == src.root
    entries = []
    for index in range(manifest.chunk_count):
        chunk = src.chunk(index)
        assert 0 < len(chunk) <= SNAPSHOT_CHUNK_ENTRIES
        entries.extend(chunk)

    dst = StateMachine(_store(tmp_path, "dst"))
    dst.adopt(manifest, entries)
    assert dst.anchor() == src.anchor()
    assert dst.reported_root == src.root
    assert dst.synced_from_snapshot
    # the adopted state answers the same reads as the source
    assert dst.read_user(b"carried") == src.read_user(b"carried")
    for block in blocks:
        digest = block.payloads[0].to_bytes()
        assert dst.read_ledger(digest) == src.read_ledger(digest)


def test_delta_entries_filter_by_round(tmp_path):
    sm = StateMachine(_store(tmp_path, "db"))
    blocks = chain(6)
    for block in blocks:
        sm.apply_block(block)
    cut = blocks[3].round
    full = sm._entries()
    delta = sm._entries(from_round=cut)
    assert len(full) == len(blocks)
    assert len(delta) == len([b for b in blocks if b.round > cut])
    assert set(delta) <= set(full)
    for _, value in delta:
        assert int.from_bytes(value[:8], "little") > cut
    # the delta manifest still anchors at the server's full cursor
    assert sm.manifest(from_round=cut).version == sm.version


def test_adopt_rejects_entries_outside_state_namespace(tmp_path):
    sm = StateMachine(_store(tmp_path, "db"))
    manifest = SnapshotManifest(1, Digest.random().to_bytes(), 1, 0, 1)
    with pytest.raises(StateError):
        sm.adopt(manifest, [(b"p" + b"\x00" * 32, b"smuggled body")])
    with pytest.raises(StateError):
        sm.adopt(manifest, [(b"s/meta", b"cursor overwrite")])
    # a poisoned snapshot must not move the cursor
    assert sm.version == 0
    assert sm.root == GENESIS_ROOT


# ---- state wire frames -----------------------------------------------------


def test_state_request_wire_roundtrip():
    origin = keys()[0][0]
    for kind in (STATE_REQ_MANIFEST, STATE_REQ_CHUNK, STATE_REQ_DELTA):
        frame = encode_state_request(kind, origin, index=3, from_round=17)
        tag, msg = decode_message(frame)
        assert tag == TAG_STATE_REQUEST
        assert (msg.kind, msg.index, msg.from_round) == (kind, 3, 17)
        assert msg.origin == origin


def test_state_manifest_wire_roundtrip():
    block = chain(2)[-1]
    qc = qc_for_block(block)
    origin = keys()[1][0]
    root = Digest.random().to_bytes()
    frame = encode_state_manifest(9, root, block.round, 42, 2, 5, qc, origin)
    tag, msg = decode_message(frame)
    assert tag == TAG_STATE_MANIFEST
    assert (msg.version, msg.root, msg.last_round) == (9, root, block.round)
    assert (msg.applied_payloads, msg.chunk_count, msg.from_round) == (42, 2, 5)
    assert msg.qc.hash == qc.hash and msg.qc.round == qc.round
    assert msg.origin == origin


def test_state_chunk_wire_roundtrip_and_cap():
    entries = [(b"s/l" + bytes([i]) * 32, bytes(8) + bytes([i])) for i in range(5)]
    frame = encode_state_chunk(4, 1, 10, entries)
    tag, msg = decode_message(frame)
    assert tag == TAG_STATE_CHUNK
    assert (msg.version, msg.index, msg.from_round) == (4, 1, 10)
    assert list(msg.entries) == entries
    assert decode_message(encode_state_chunk(1, 0, 0, []))[1].entries == ()
    with pytest.raises(ValueError):
        encode_state_chunk(
            1, 0, 0, [(b"k", b"v")] * (MAX_STATE_CHUNK_ENTRIES + 1)
        )


def test_state_read_wire_roundtrip():
    for space in (STATE_READ_LEDGER, STATE_READ_USER):
        tag, msg = decode_message(encode_state_read(space, b"some-key"))
        assert tag == TAG_STATE_READ
        assert msg == (space, b"some-key")
    # unknown read space must be a clean decode error
    bad = bytearray(encode_state_read(STATE_READ_USER, b"k"))
    bad[2] = 99
    with pytest.raises(SerializationError):
        decode_message(bytes(bad))


def test_state_value_reply_roundtrip():
    root = Digest.random().to_bytes()
    frame = encode_state_value(True, 11, root, 13, 9, b"payload-value")
    reply = decode_state_value(frame)
    assert reply.found is True
    assert (reply.state_version, reply.root) == (11, root)
    assert (reply.last_round, reply.entry_round) == (13, 9)
    assert reply.value == b"payload-value"
    assert frame[0] == STATE_VALUE_TAG
    # non-reply frames (e.g. ingest ACKs) pass through as None
    assert decode_state_value(b"Ack") is None
    assert decode_state_value(b"") is None
    miss = decode_state_value(
        encode_state_value(False, 11, root, 13, 0, b"")
    )
    assert miss.found is False and miss.value == b""
