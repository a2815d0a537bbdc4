"""Native C++ WAL engine: parity with the Python engine, crash-kill
recovery, compaction, fsync modes (reference store durability semantics,
store/src/lib.rs + SURVEY.md §5 "the store IS the checkpoint")."""

from __future__ import annotations

import os
import struct
import subprocess
import sys

import pytest

from hotstuff_tpu.store.engine import WalEngine

try:
    from hotstuff_tpu.store.native import NativeEngine

    _HAVE_NATIVE = True
except (ImportError, OSError):  # no compiler in this environment
    _HAVE_NATIVE = False

needs_native = pytest.mark.skipif(not _HAVE_NATIVE, reason="native lib not built")


@needs_native
def test_native_put_get_delete_roundtrip(tmp_path):
    e = NativeEngine(str(tmp_path / "db"))
    e.put(b"a", b"1")
    e.put(b"b", b"2" * 1000)
    e.put(b"a", b"3")  # overwrite
    e.delete(b"b")
    assert e.get(b"a") == b"3"
    assert e.get(b"b") is None
    assert e.get(b"missing") is None
    assert len(e) == 1
    assert set(e.keys()) == {b"a"}
    e.put(b"", b"empty-key")  # empty key and value edge cases
    e.put(b"ev", b"")
    assert e.get(b"") == b"empty-key"
    assert e.get(b"ev") == b""
    e.close()


@needs_native
def test_native_reopen_recovers(tmp_path):
    path = str(tmp_path / "db")
    e = NativeEngine(path)
    for i in range(100):
        e.put(f"k{i}".encode(), f"v{i}".encode() * 10)
    e.delete(b"k50")
    e.close()
    e2 = NativeEngine(path)
    assert len(e2) == 99
    assert e2.get(b"k7") == b"v7" * 10
    assert e2.get(b"k50") is None
    e2.close()


@needs_native
def test_cross_engine_wal_interop(tmp_path):
    """Python and C++ engines share the WAL format bit-for-bit."""
    path = str(tmp_path / "db")
    w = WalEngine(path)
    w.put(b"py", b"from-python")
    w.delete(b"gone")
    w.close()
    e = NativeEngine(path)
    assert e.get(b"py") == b"from-python"
    e.put(b"cc", b"from-cpp")
    e.close()
    w2 = WalEngine(path)
    assert w2.get(b"py") == b"from-python"
    assert w2.get(b"cc") == b"from-cpp"
    w2.close()


@needs_native
def test_native_torn_tail_truncated(tmp_path):
    """A torn (half-written) trailing record is discarded and truncated."""
    path = str(tmp_path / "db")
    e = NativeEngine(path)
    e.put(b"good", b"value")
    e.close()
    wal = os.path.join(path, "wal.log")
    with open(wal, "ab") as f:
        f.write(struct.pack("<II", 4, 100))  # header promises 100-byte value
        f.write(b"torn")  # ...but the process died here
    e2 = NativeEngine(path)
    assert e2.get(b"good") == b"value"
    assert len(e2) == 1
    e2.close()
    # tail was truncated: a fresh append replays cleanly
    e3 = NativeEngine(path)
    e3.put(b"after", b"recovery")
    e3.close()
    e4 = NativeEngine(path)
    assert e4.get(b"after") == b"recovery"
    assert len(e4) == 2
    e4.close()


_KILL_SCRIPT = r"""
import os, sys
sys.path.insert(0, {root!r})
from hotstuff_tpu.store.native import NativeEngine
e = NativeEngine({path!r}, fsync_mode=1)
for i in range(50):
    e.put(f"key{{i}}".encode(), b"x" * 100)
os.kill(os.getpid(), 9)  # die without close()
"""


@needs_native
def test_native_survives_sigkill(tmp_path):
    """Process killed mid-sequence (no close): every acknowledged put is
    recovered on reopen (VERDICT r1 item 9)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = str(tmp_path / "db")
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_SCRIPT.format(root=root, path=path)],
        capture_output=True,
        timeout=60,
    )
    assert proc.returncode == -9  # SIGKILL
    e = NativeEngine(path)
    assert len(e) == 50
    for i in range(50):
        assert e.get(f"key{i}".encode()) == b"x" * 100
    e.close()


@needs_native
def test_native_compaction_bounds_wal(tmp_path):
    """Overwriting the same keys grows the log; reopen compacts it."""
    path = str(tmp_path / "db")
    e = NativeEngine(path)
    for round_ in range(300):
        for k in range(10):
            e.put(f"key{k}".encode(), bytes([round_ % 256]) * 1024)
    grown = e.wal_bytes()
    e.close()
    assert grown > 2 * 10 * 1100  # lots of dead records
    e2 = NativeEngine(path)
    assert e2.wal_bytes() < grown / 10  # compacted on open
    assert len(e2) == 10
    for k in range(10):
        assert e2.get(f"key{k}".encode()) == bytes([299 % 256]) * 1024
    e2.close()


def test_python_wal_compaction_and_fsync(tmp_path):
    """The pure-Python engine has the same compaction + fsync options."""
    path = str(tmp_path / "db")
    e = WalEngine(path, fsync_mode=1)
    for round_ in range(300):
        for k in range(10):
            e.put(f"key{k}".encode(), bytes([round_ % 256]) * 1024)
    e.close()
    grown = os.path.getsize(os.path.join(path, "wal.log"))
    e2 = WalEngine(path)
    compacted = os.path.getsize(os.path.join(path, "wal.log"))
    assert compacted < grown / 10
    assert len(e2) == 10
    e2.close()


@needs_native
def test_store_actor_uses_native_engine(tmp_path):
    """open_engine prefers the native engine when the library is built."""
    from hotstuff_tpu.store import open_engine

    e = open_engine(str(tmp_path / "db"))
    assert type(e).__name__ == "NativeEngine"
    e.close()


# ---- the write batch --------------------------------------------------------


@needs_native
@pytest.mark.parametrize("writer", ["wal", "native"])
def test_batch_written_by_one_engine_is_recovered_by_the_other(tmp_path, writer):
    """A batch leaves plain WAL records: the other engine replays them,
    appends a batch of its own, and the first reads both back."""
    first, second = (
        (WalEngine, NativeEngine) if writer == "wal" else (NativeEngine, WalEngine)
    )
    path = str(tmp_path / "db")
    batch = [(b"s/l" + bytes([i]) * 32, struct.pack("<QI", 9, i)) for i in range(21)]
    batch.append((b"s/meta", b"m" * 88))
    e = first(path)
    e.put(b"gone", b"soon")
    e.put_many(batch)
    e.delete(b"gone")
    e.close()
    e2 = second(path)
    assert len(e2) == len(batch)
    assert e2.get_many([k for k, _ in batch]) == [v for _, v in batch]
    e2.put_many([(b"s/meta", b"n" * 88), (b"from", b"the-other")])
    e2.close()
    e3 = first(path)
    assert e3.get(b"s/meta") == b"n" * 88
    assert e3.get(b"from") == b"the-other"
    assert e3.get(b"gone") is None
    assert len(e3) == len(batch) + 1
    e3.close()


@needs_native
@pytest.mark.parametrize(
    "buf",
    [
        struct.pack("<II", 1, 1) + b"k",  # value overruns the buffer
        struct.pack("<II", 1, 1) + b"kv" + b"\x01\x00\x00",  # torn header
        struct.pack("<II", 1, 0xFFFFFFFF) + b"k",  # a tombstone is no put
    ],
    ids=["overrun", "torn-header", "tombstone"],
)
def test_native_refuses_a_malformed_batch_whole(tmp_path, buf):
    """hs_put_many checks the packed buffer before it writes: a bad
    batch leaves the log and the index as they were."""
    path = str(tmp_path / "db")
    e = NativeEngine(path)
    e.put(b"a", b"1")
    good = struct.pack("<II", 1, 1) + b"a2"
    assert e._lib.hs_put_many(e._h, good + buf, len(good + buf)) == -1
    assert e.get(b"a") == b"1" and len(e) == 1
    before = e.wal_bytes()
    assert e._lib.hs_put_many(e._h, good, len(good)) == 0
    assert e.get(b"a") == b"2" and e.wal_bytes() == before + len(good)
    e.close()


_SHIM_C = r"""
#define _GNU_SOURCE
#include <fcntl.h>
#include <stdio.h>
#include <stdlib.h>
#include <sys/syscall.h>
#include <unistd.h>

static int logfd = -1;

static void note(const char* op, int fd, long n) {
  if (logfd < 0) {
    const char* p = getenv("WAL_SHIM_LOG");
    if (!p) return;
    logfd = syscall(SYS_openat, AT_FDCWD, p, O_WRONLY | O_APPEND | O_CREAT, 0644);
    if (logfd < 0) return;
  }
  char b[64];
  int m = snprintf(b, sizeof b, "%s %d %ld\n", op, fd, n);
  syscall(SYS_write, logfd, b, m);
}

ssize_t write(int fd, const void* p, size_t n) {
  note("write", fd, (long)n);
  return syscall(SYS_write, fd, p, n);
}
int fdatasync(int fd) { note("sync", fd, 0); return syscall(SYS_fdatasync, fd); }
int fsync(int fd) { note("sync", fd, 0); return syscall(SYS_fsync, fd); }
"""

_SHIM_SCRIPT = r"""
import os, sys
sys.path.insert(0, {root!r})
from hotstuff_tpu.store.engine import WalEngine
from hotstuff_tpu.store.native import NativeEngine
e = {cls}({path!r}, fsync_mode={mode})
e.put(b"warm", b"up")
fd = next(int(n) for n in os.listdir("/proc/self/fd")
          if os.path.realpath("/proc/self/fd/" + n).endswith("wal.log"))
os.write(1, b"fd %d\n" % fd)
os.write(fd, b"")  # a marker in the shim's log: the batch follows
e.put_many([(b"s/l%d" % i, b"v" * i) for i in range(21)] + [(b"s/meta", b"m")])
os.write(fd, b"")
e.close()
"""


@pytest.fixture(scope="module")
def wal_shim(tmp_path_factory):
    """An LD_PRELOAD library that notes every write and sync a process
    makes, by descriptor: both engines reach the WAL through libc."""
    d = tmp_path_factory.mktemp("shim")
    src, lib = d / "shim.c", d / "shim.so"
    src.write_text(_SHIM_C)
    try:
        subprocess.run(
            ["gcc", "-O1", "-shared", "-fPIC", "-o", str(lib), str(src)],
            check=True, capture_output=True, timeout=60,
        )
    except (OSError, subprocess.SubprocessError) as e:
        pytest.skip(f"cannot build the shim: {e}")
    return str(lib)


@needs_native
@pytest.mark.parametrize("mode", [0, 1])
@pytest.mark.parametrize("cls", ["WalEngine", "NativeEngine"])
def test_batch_is_one_write_and_in_mode_1_one_sync_after_it(
    tmp_path, wal_shim, cls, mode
):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path, log = str(tmp_path / "db"), str(tmp_path / "calls.log")
    proc = subprocess.run(
        [sys.executable, "-c",
         _SHIM_SCRIPT.format(root=root, path=path, cls=cls, mode=mode)],
        capture_output=True, timeout=60,
        env={**os.environ, "LD_PRELOAD": wal_shim, "WAL_SHIM_LOG": log},
    )
    assert proc.returncode == 0, proc.stderr.decode()
    fd = proc.stdout.split()[1].decode()
    calls = [
        (op, int(n)) for op, f, n in
        (line.split() for line in open(log).read().splitlines()) if f == fd
    ]
    first = calls.index(("write", 0))
    last = calls.index(("write", 0), first + 1)
    size = sum(8 + len(b"s/l%d" % i) + i for i in range(21)) + 8 + 6 + 1
    expect = [("write", size)] + ([("sync", 0)] if mode == 1 else [])
    assert calls[first + 1 : last] == expect
    e = WalEngine(path)
    assert len(e) == 23 and e.get(b"s/meta") == b"m"
    e.close()
