"""Store tests — ports of the reference's store_tests.rs (create, read/write,
missing key, notify_read before/after write) plus WAL crash-recovery cases
the reference lacks (SURVEY.md §4 gaps)."""

import asyncio
import os

import pytest

from hotstuff_tpu.store import Store, WalEngine


def run(coro):
    return asyncio.run(coro)


def test_create_store(tmp_path):
    store = Store(str(tmp_path / "db"))
    store.close()


def test_read_write_value(tmp_path):
    async def body():
        store = Store(str(tmp_path / "db"))
        await store.write(b"hello", b"world")
        assert await store.read(b"hello") == b"world"
        store.close()

    run(body())


def test_read_unknown_key(tmp_path):
    async def body():
        store = Store(str(tmp_path / "db"))
        assert await store.read(b"nope") is None
        store.close()

    run(body())


def test_read_notify_existing(tmp_path):
    async def body():
        store = Store(str(tmp_path / "db"))
        await store.write(b"k", b"v")
        assert await store.notify_read(b"k") == b"v"
        store.close()

    run(body())


def test_read_notify_parks_until_write(tmp_path):
    async def body():
        store = Store(str(tmp_path / "db"))
        waiter = asyncio.create_task(store.notify_read(b"later"))
        await asyncio.sleep(0.05)
        assert not waiter.done()
        await store.write(b"later", b"arrived")
        assert await asyncio.wait_for(waiter, 1) == b"arrived"
        # multiple waiters on one key all resolve
        w1 = asyncio.create_task(store.notify_read(b"multi"))
        w2 = asyncio.create_task(store.notify_read(b"multi"))
        await asyncio.sleep(0.05)
        await store.write(b"multi", b"x")
        assert await asyncio.wait_for(asyncio.gather(w1, w2), 1) == [b"x", b"x"]
        store.close()

    run(body())


def test_persistence_across_reopen(tmp_path):
    path = str(tmp_path / "db")

    async def write_phase():
        store = Store(path)
        for i in range(100):
            await store.write(b"key-%d" % i, b"value-%d" % i)
        await store.read(b"key-0")  # drain the queue
        store.close()

    async def read_phase():
        store = Store(path)
        for i in range(100):
            assert await store.read(b"key-%d" % i) == b"value-%d" % i
        store.close()

    run(write_phase())
    run(read_phase())


def test_torn_tail_record_discarded(tmp_path):
    path = str(tmp_path / "db")
    eng = WalEngine(path)
    eng.put(b"good", b"value")
    eng.close()
    # simulate a crash mid-append
    with open(os.path.join(path, "wal.log"), "ab") as f:
        f.write(b"\x10\x00\x00\x00\x10\x00\x00\x00partial")
    eng2 = WalEngine(path)
    assert eng2.get(b"good") == b"value"
    assert len(eng2) == 1
    # engine still writable after recovery
    eng2.put(b"after", b"crash")
    assert eng2.get(b"after") == b"crash"
    eng2.close()
    # records written after recovery must survive a SECOND reopen
    eng3 = WalEngine(path)
    assert eng3.get(b"good") == b"value"
    assert eng3.get(b"after") == b"crash"
    eng3.close()


def test_torn_tail_every_byte_offset(tmp_path):
    """Crash-chop the log at EVERY byte offset inside the final record.

    Whatever prefix of the last append survives the crash, replay must keep
    all fully-written records, drop the torn one, truncate the tail, and
    leave the engine writable — and a further reopen must see the post-crash
    writes."""
    key, value = b"final-key", b"final-value!"
    record_len = 8 + len(key) + len(value)
    for cut in range(record_len):
        path = str(tmp_path / ("db-%d" % cut))
        eng = WalEngine(path)
        eng.put(b"keep-a", b"1")
        eng.put(b"keep-b", b"2")
        eng.put(key, value)
        eng.close()
        wal = os.path.join(path, "wal.log")
        full = os.path.getsize(wal)
        with open(wal, "ab") as f:
            f.truncate(full - record_len + cut)
        eng2 = WalEngine(path)
        assert eng2.get(b"keep-a") == b"1"
        assert eng2.get(b"keep-b") == b"2"
        assert eng2.get(key) is None
        assert len(eng2) == 2
        eng2.put(b"post", b"crash")
        eng2.close()
        eng3 = WalEngine(path)
        assert eng3.get(b"keep-a") == b"1"
        assert eng3.get(key) is None
        assert eng3.get(b"post") == b"crash"
        eng3.close()


def test_torn_tail_delete_record(tmp_path):
    """A torn trailing tombstone must not delete the key it targeted."""
    path = str(tmp_path / "db")
    eng = WalEngine(path)
    eng.put(b"victim", b"alive")
    eng.delete(b"victim")
    eng.close()
    wal = os.path.join(path, "wal.log")
    with open(wal, "ab") as f:
        f.truncate(os.path.getsize(wal) - 1)
    eng2 = WalEngine(path)
    assert eng2.get(b"victim") == b"alive"
    eng2.close()


def test_delete_tombstone_survives_reopen(tmp_path):
    path = str(tmp_path / "db")
    eng = WalEngine(path)
    eng.put(b"a", b"1")
    eng.put(b"b", b"2")
    eng.delete(b"a")
    eng.close()
    eng2 = WalEngine(path)
    assert eng2.get(b"a") is None
    assert eng2.get(b"b") == b"2"
    eng2.close()


def test_overwrite_uses_latest(tmp_path):
    path = str(tmp_path / "db")
    eng = WalEngine(path)
    eng.put(b"k", b"old")
    eng.put(b"k", b"new")
    eng.close()
    eng2 = WalEngine(path)
    assert eng2.get(b"k") == b"new"
    eng2.close()


# ---- the write batch: one WAL append for several records -------------------


def _wal_bytes(path: str) -> bytes:
    with open(os.path.join(path, "wal.log"), "rb") as f:
        return f.read()


#: a block's worth of records: sizes from empty to past a file buffer
BATCH = [
    (b"s/l" + bytes([i]) * 32, b"%d" % i * (i % 7)) for i in range(20)
] + [(b"", b"empty-key"), (b"ev", b""), (b"big", b"\xab" * 20000),
     (b"s/meta", b"cursor")]


def test_batch_leaves_the_bytes_and_index_of_per_record_puts(
    tmp_path, engine_cls
):
    one, many = str(tmp_path / "one"), str(tmp_path / "many")
    a, b = engine_cls(one), engine_cls(many)
    a.put(b"before", b"x")
    b.put(b"before", b"x")
    for key, value in BATCH:
        a.put(key, value)
    b.put_many(BATCH)
    b.put_many([])  # nothing to write, nothing written
    a.put(b"after", b"y")
    b.put(b"after", b"y")
    keys = [key for key, _ in BATCH] + [b"before", b"after", b"missing"]
    assert b.get_many(keys) == [a.get(key) for key in keys]
    assert a.get_many(keys) == b.get_many(keys)
    assert b.get_many([]) == []
    assert sorted(a.keys()) == sorted(b.keys())
    a.close()
    b.close()
    assert _wal_bytes(many) == _wal_bytes(one)


def test_batch_with_a_key_twice_keeps_the_last(tmp_path, engine_cls):
    path = str(tmp_path / "db")
    e = engine_cls(path)
    e.put(b"k", b"old")
    e.put_many([(b"k", b"first"), (b"other", b"1"), (b"k", b"last")])
    assert e.get(b"k") == b"last"
    assert len(e) == 2
    e.close()
    e2 = engine_cls(path)
    assert e2.get_many([b"k", b"other"]) == [b"last", b"1"]
    e2.close()


def test_batch_counts_one_append_whatever_its_records(tmp_path, engine_cls):
    from hotstuff_tpu.store.engine import WAL_COUNTS

    e = engine_cls(str(tmp_path / "db"))
    a0, r0 = WAL_COUNTS.appends, WAL_COUNTS.records
    e.put_many(BATCH)
    e.put(b"k", b"v")
    e.delete(b"k")
    e.put_many([])
    e.get_many([b"k"])
    e.close()
    assert WAL_COUNTS.appends - a0 == 3
    assert WAL_COUNTS.records - r0 == len(BATCH) + 2


def test_batch_chopped_at_every_byte_replays_to_a_prefix(tmp_path, engine_cls):
    """Crash-chop the log at EVERY byte offset inside a batch: replay
    keeps the records before the batch and the whole records of the
    batch before the cut, in order, drops the rest, and the engine
    stays writable."""
    batch = [(b"a", b"new-a"), (b"s/l" + b"\x07" * 32, b"ledger!"),
             (b"a", b"newest-a"), (b"s/meta", b"m" * 30)]
    src = str(tmp_path / "src")
    e = engine_cls(src)
    e.put(b"a", b"old-a")
    e.put(b"keep", b"1")
    e.close()
    head = _wal_bytes(src)
    e = engine_cls(src)
    e.put_many(batch)
    e.close()
    full = _wal_bytes(src)
    ends, at = [], len(head)
    for key, value in batch:
        at += 8 + len(key) + len(value)
        ends.append(at)
    assert at == len(full)
    for cut in range(len(head), len(full) + 1):
        path = str(tmp_path / ("db-%d" % cut))
        os.makedirs(path)
        with open(os.path.join(path, "wal.log"), "wb") as f:
            f.write(full[:cut])
        whole = sum(1 for end in ends if end <= cut)
        expect = {b"a": b"old-a", b"keep": b"1"}
        expect.update(batch[:whole])
        e2 = engine_cls(path)
        assert {k: e2.get(k) for k in e2.keys()} == expect, cut
        e2.put(b"post", b"crash")
        e2.close()
        kept = ends[whole - 1] if whole else len(head)
        assert _wal_bytes(path)[:kept] == full[:kept]
        e3 = engine_cls(path)
        assert e3.get(b"post") == b"crash"
        assert e3.get(b"a") == expect[b"a"]
        e3.close()


def test_write_many_wakes_every_keys_readers_and_refuses_a_closed_store(
    tmp_path, engine_cls
):
    async def body():
        path = str(tmp_path / "db")
        store = Store(path, engine=engine_cls(path))
        w1 = asyncio.create_task(store.notify_read(b"block"))
        w2 = asyncio.create_task(store.notify_read(b"block"))
        w3 = asyncio.create_task(store.notify_read(b"index"))
        idle = asyncio.create_task(store.notify_read(b"never"))
        await asyncio.sleep(0.05)
        assert not (w1.done() or w2.done() or w3.done())
        await store.write_many(
            [(b"block", b"B"), (b"index", b"I"), (b"latest", b"L")]
        )
        got = await asyncio.wait_for(asyncio.gather(w1, w2, w3), 1)
        assert got == [b"B", b"B", b"I"]
        assert not idle.done()
        assert await store.read(b"latest") == b"L"
        assert await store.notify_read(b"index") == b"I"
        store.close()
        await asyncio.gather(idle, return_exceptions=True)
        assert idle.cancelled()
        with pytest.raises(RuntimeError):
            await store.write_many([(b"k", b"v")])

    run(body())
