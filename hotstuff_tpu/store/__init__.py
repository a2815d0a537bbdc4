"""Store: a single-writer actor serializing all storage access.

Parity target: the reference ``store`` crate (store/src/lib.rs:15-92):
one task owns the database; clients talk to it through a channel of
Write/Read/NotifyRead commands. ``notify_read`` is the blocking-read
primitive the synchronizer's "wait for a missing parent block" is built on
(reference store/src/lib.rs:29,80-92): if the key is missing, the caller's
future is parked in an obligations map and resolved by a later write of
that key.
"""

from __future__ import annotations

import asyncio
from collections import deque

from ..telemetry import spans as _spans
from .engine import Engine, WalEngine


def open_engine(
    path: str, prefer_native: bool = True, fsync_mode: int = 0
) -> Engine:
    """Open the best available engine at ``path`` (C++ if built, else the
    pure-Python WAL).  Both speak the same on-disk format.  fsync_mode:
    0 = flush per append, 1 = fsync per append, 2 = fsync on close; an
    append is one put, one delete or one whole write batch."""
    if prefer_native:
        try:
            from .native import NativeEngine  # noqa: PLC0415

            return NativeEngine(path, fsync_mode)
        except (ImportError, OSError):
            pass
    return WalEngine(path, fsync_mode)


class Store:
    """Single-writer store with the reference's command semantics,
    executed INLINE on the event loop.

    The reference funnels Write/Read/NotifyRead through a channel to one
    owning task (store/src/lib.rs:27-62) because tokio tasks run on many
    threads.  Under asyncio there is exactly one thread, so the loop
    itself already provides the single-writer discipline — routing every
    operation through a queue would only add two task switches (~45 us
    each, profiled) per access on the consensus hot path.  Operations
    therefore execute synchronously in the caller's coroutine, in call
    order, which is the same total order a queue would impose.  The
    ``notify_read`` obligations map (park a future until a later write
    of that key) is preserved unchanged — it is the primitive the
    synchronizer's missing-parent wait is built on.
    """

    def __init__(
        self, path: str, engine: Engine | None = None, node: str = ""
    ):
        self.engine = engine if engine is not None else open_engine(path)
        self.node = node  # the owner's short name: labels the spans
        self._obligations: dict[bytes, deque[asyncio.Future]] = {}
        self._closed = False

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("Store is closed")

    def _wake(self, key: bytes, value: bytes) -> None:
        waiters = self._obligations.pop(key, None)
        if waiters:
            for fut in waiters:
                if not fut.done():
                    fut.set_result(value)

    async def write(self, key: bytes, value: bytes) -> None:
        self._check_open()
        with _spans.span("store.write", node=self.node):
            self.engine.put(key, value)
            self._wake(key, value)

    async def write_many(self, pairs: list[tuple[bytes, bytes]]) -> None:
        """Write the records as one batch: one WAL append, in order, in
        the log (and synced as the engine's ``fsync_mode`` says) before
        this returns.  A crash inside the append leaves a prefix of
        whole records, as a crash between as many ``write`` calls
        would."""
        self._check_open()
        with _spans.span("store.write", node=self.node):
            self.engine.put_many(pairs)
            if self._obligations:
                for key, value in pairs:
                    self._wake(key, value)

    async def read(self, key: bytes) -> bytes | None:
        self._check_open()
        with _spans.span("store.read", node=self.node):
            return self.engine.get(key)

    async def delete(self, key: bytes) -> None:
        """Remove a key (no obligation wake-up — deletes never resolve a
        parked notify_read).  Used by the payload-body budget's eviction
        of uncommitted producer bodies."""
        self._check_open()
        with _spans.span("store.write", node=self.node):
            self.engine.delete(key)

    async def notify_read(self, key: bytes) -> bytes:
        """Read that resolves when the key exists (possibly immediately)."""
        self._check_open()
        with _spans.span("store.read", node=self.node):
            value = self.engine.get(key)
        if value is not None:
            return value
        fut = asyncio.get_running_loop().create_future()
        self._obligations.setdefault(key, deque()).append(fut)
        return await fut

    def cancel_notify(self, key: bytes) -> None:
        """Cancel and drop every future parked on ``key``.  The
        synchronizer calls this when it gives up on a missing parent:
        waiter tasks cancelled from outside leave their (cancelled)
        futures in the obligations deque, and absent a later write of
        that exact key the entry would pin memory forever."""
        waiters = self._obligations.pop(key, None)
        if waiters:
            for fut in waiters:
                if not fut.done():
                    fut.cancel()

    def close(self) -> None:
        self._closed = True
        for waiters in self._obligations.values():
            for fut in waiters:
                if not fut.done():
                    fut.cancel()
        self._obligations.clear()
        self.engine.close()


__all__ = ["Store", "Engine", "WalEngine", "open_engine"]
