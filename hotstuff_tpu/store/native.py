"""ctypes bridge to the native C++ WAL engine (native/store_engine.cpp).

Same on-disk WAL format as the pure-Python ``WalEngine``
(hotstuff_tpu/store/engine.py) — either implementation can recover the
other's files.  The shared library is built with ``make -C native`` (or
automatically on first import when a compiler is available); set
``HOTSTUFF_STORE_NATIVE=0`` to force the Python engine.

Durability: ``fsync_mode`` 0 = flush per append (process-crash safe),
1 = fdatasync per append (power-loss safe), 2 = fdatasync on close.  An
append is one ``put``, one ``delete`` or one whole ``put_many`` batch:
the batch crosses into the library once, packed as the WAL records it
becomes, and is written with one ``write``.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
from typing import Iterator

from .engine import TOMBSTONE, WAL_COUNTS, pack_records

_LIB_NAME = "libhs_store.so"
_U32 = struct.Struct("<I")


def _native_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "native",
    )


def _load_lib() -> ctypes.CDLL:
    if os.environ.get("HOTSTUFF_STORE_NATIVE") == "0":
        raise ImportError("native engine disabled via HOTSTUFF_STORE_NATIVE=0")
    path = os.path.join(_native_dir(), "build", _LIB_NAME)
    if not os.path.exists(path):
        # one best-effort build; races are harmless (make is idempotent)
        try:
            subprocess.run(
                ["make", "-C", _native_dir()],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as e:
            raise ImportError(f"cannot build {_LIB_NAME}: {e}") from e
    lib = ctypes.CDLL(path)
    lib.hs_open.restype = ctypes.c_void_p
    lib.hs_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.hs_put.restype = ctypes.c_int
    lib.hs_put.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.c_uint32,
    ]
    lib.hs_put_many.restype = ctypes.c_int
    lib.hs_put_many.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint64,
    ]
    lib.hs_get_many.restype = ctypes.c_int
    lib.hs_get_many.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.hs_get.restype = ctypes.c_int
    lib.hs_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.hs_delete.restype = ctypes.c_int
    lib.hs_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.hs_keys_blob.restype = ctypes.c_int
    lib.hs_keys_blob.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.hs_count.restype = ctypes.c_uint64
    lib.hs_count.argtypes = [ctypes.c_void_p]
    lib.hs_wal_bytes.restype = ctypes.c_uint64
    lib.hs_wal_bytes.argtypes = [ctypes.c_void_p]
    lib.hs_compact.restype = ctypes.c_int
    lib.hs_compact.argtypes = [ctypes.c_void_p]
    lib.hs_free.restype = None
    lib.hs_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    lib.hs_close.restype = None
    lib.hs_close.argtypes = [ctypes.c_void_p]
    return lib


_lib: ctypes.CDLL | None = None


def _get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        _lib = _load_lib()
    return _lib


class NativeEngine:
    """Engine-protocol adapter over the C++ WAL engine."""

    def __init__(self, path: str, fsync_mode: int = 0):
        self._lib = _get_lib()
        self._h = self._lib.hs_open(path.encode(), fsync_mode)
        if not self._h:
            raise OSError(f"hs_open failed for {path!r}")
        self.path = path

    def put(self, key: bytes, value: bytes) -> None:
        if self._lib.hs_put(self._h, key, len(key), value, len(value)) != 0:
            raise OSError("hs_put failed")
        WAL_COUNTS.add(1)

    def put_many(self, pairs: list[tuple[bytes, bytes]]) -> None:
        """The batch as one append, in one crossing."""
        if not pairs:
            return
        buf = pack_records(pairs)
        if self._lib.hs_put_many(self._h, buf, len(buf)) != 0:
            raise OSError("hs_put_many failed")
        WAL_COUNTS.add(len(pairs))

    def get(self, key: bytes) -> bytes | None:
        out = ctypes.POINTER(ctypes.c_uint8)()
        outlen = ctypes.c_uint32()
        rc = self._lib.hs_get(
            self._h, key, len(key), ctypes.byref(out), ctypes.byref(outlen)
        )
        if rc == -1:
            return None
        if rc != 0:
            raise OSError("hs_get failed")
        try:
            return ctypes.string_at(out, outlen.value)
        finally:
            self._lib.hs_free(out)

    def get_many(self, keys: list[bytes]) -> list[bytes | None]:
        """A value or None a key, in one crossing."""
        count = len(keys)
        if not count:
            return []
        out = ctypes.POINTER(ctypes.c_uint8)()
        outlen = ctypes.c_uint64()
        if self._lib.hs_get_many(
            self._h,
            struct.pack("<%dI" % count, *map(len, keys)),
            count,
            b"".join(keys),
            ctypes.byref(out),
            ctypes.byref(outlen),
        ):
            raise OSError("hs_get_many failed")
        try:
            blob = ctypes.string_at(out, outlen.value)
        finally:
            self._lib.hs_free(out)
        values: list[bytes | None] = []
        off = 4 * count
        for vlen in struct.unpack_from("<%dI" % count, blob):
            if vlen == TOMBSTONE:
                values.append(None)
            else:
                values.append(blob[off : off + vlen])
                off += vlen
        return values

    def delete(self, key: bytes) -> None:
        if self._lib.hs_delete(self._h, key, len(key)) != 0:
            raise OSError("hs_delete failed")
        WAL_COUNTS.add(1)

    def keys(self) -> Iterator[bytes]:
        out = ctypes.POINTER(ctypes.c_uint8)()
        outlen = ctypes.c_uint64()
        if self._lib.hs_keys_blob(self._h, ctypes.byref(out), ctypes.byref(outlen)):
            raise OSError("hs_keys_blob failed")
        try:
            blob = ctypes.string_at(out, outlen.value)
        finally:
            self._lib.hs_free(out)
        (count,) = _U32.unpack_from(blob, 0)
        off = 4
        result = []
        for _ in range(count):
            (klen,) = _U32.unpack_from(blob, off)
            off += 4
            result.append(blob[off : off + klen])
            off += klen
        return iter(result)

    def __len__(self) -> int:
        return int(self._lib.hs_count(self._h))

    def wal_bytes(self) -> int:
        return int(self._lib.hs_wal_bytes(self._h))

    def compact(self) -> None:
        if self._lib.hs_compact(self._h) != 0:
            raise OSError("hs_compact failed")

    def close(self) -> None:
        if self._h:
            self._lib.hs_close(self._h)
            self._h = None
