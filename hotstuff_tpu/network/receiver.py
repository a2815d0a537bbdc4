"""Network receiver: TCP listener dispatching frames to a handler.

Parity target: reference ``Receiver<Handler>`` (network/src/receiver.rs:
18-89): bind a TCP listener, spawn one runner per accepted connection,
decode length-delimited frames, hand each to ``handler.dispatch(writer,
bytes)``. The handler gets the connection's writer so it can send replies
or ACKs back on the same socket (the proposer's quorum-ACK back-pressure
depends on this, SURVEY.md §2.3).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Protocol

from ..crypto.async_service import ingest_note_frame, zero_copy_ingest
from .framing import FramingError, read_frame, send_frame, set_nodelay

log = logging.getLogger(__name__)

#: connections the kernel holds for ``accept`` before it drops a peer's
#: SYN (1 s before that peer retries): tokio's ``TcpListener::bind``
#: value, which the reference listens with.  asyncio's default of 100
#: overflows when a committee's 255 other members open their vote
#: connections to a new leader at once.  The kernel caps it at
#: ``net.core.somaxconn``.
ACCEPT_BACKLOG = 1024

#: wire tags mirrored from consensus/wire.py — importing it here would
#: cycle (consensus imports this module for the Writer protocol);
#: tests/test_wire_fuzz.py asserts these against the live constants
_TAG_VOTE = 1
_TAG_PRODUCER_V2 = 6


async def dispatch_ingest(handler, writer, frame: bytes) -> None:
    """Frame dispatch through the zero-copy ingest taps (ISSUE 20),
    shared by the asyncio and native receivers.

    Vote frames are additionally noted to the native wave packer — the
    verify service later adopts the packed digest/pk/sig columns
    instead of flattening Python claim tuples.  Batched producer-v2
    frames parse natively into a digest column + body spans and skip
    per-item payload tuples entirely when the handler exposes
    ``dispatch_producer_v2``.  Every miss — plane disabled, native
    library unavailable, handler without the fast path, frame the
    native parser rejects — falls through to ``handler.dispatch``
    unchanged (the differential fuzz corpus pins native and Python
    accept/reject to byte parity, so only frames BOTH reject ever
    double-parse)."""
    if frame:
        tag = frame[0]
        if tag == _TAG_VOTE:
            ingest_note_frame(frame)
        elif tag == _TAG_PRODUCER_V2:
            fast = getattr(handler, "dispatch_producer_v2", None)
            if fast is not None and zero_copy_ingest() is not None:
                from ..crypto import native_ed25519

                parsed = native_ed25519.parse_producer(frame)
                if parsed is not None:
                    digests, spans = parsed
                    await fast(writer, frame, digests, spans)
                    return
    await handler.dispatch(writer, frame)


class Writer:
    """Reply-channel handed to MessageHandler.dispatch."""

    def __init__(
        self, stream_writer: asyncio.StreamWriter, flows=None, node: str = ""
    ):
        self._writer = stream_writer
        self._flows = flows
        self._node = node

    async def send(self, payload: bytes) -> None:
        # replies (ACKs, state-read values) leave on the accepted
        # socket, not through a sender — charge their egress here
        if self._flows is not None:
            self._flows.tx(self.peer, payload)
        await send_frame(self._writer, payload, self._node)

    @property
    def peer(self):
        return self._writer.get_extra_info("peername")


class MessageHandler(Protocol):
    async def dispatch(self, writer: Writer, message: bytes) -> None: ...


class Receiver:
    """Listens on ``address`` and dispatches every frame to ``handler``.

    ``fault_plane`` (chaos plane, faults/plane.py): inbound faulting is
    all-or-nothing — accepted connections arrive from ephemeral ports,
    so frames can't be attributed to a committee peer; committee-pair
    partitions are fully enforced sender-side (every node shares the
    scenario spec).  The receiver-side cut exists for ``isolate``
    windows, where frames from planeless senders (benchmark clients)
    must die too."""

    def __init__(
        self,
        host: str,
        port: int,
        handler: MessageHandler,
        fault_plane=None,
        flows=None,
    ):
        self.host = host
        self.port = port
        self.handler = handler
        self._faults = fault_plane
        self._flows = flows
        #: the ``node`` id of this listener's spans: the handler's
        self._node = getattr(handler, "node", "")
        self._server: asyncio.AbstractServer | None = None
        # insertion-ordered (dict-as-set): shutdown closes connections
        # in accept order, so teardown is reproducible — a plain set
        # iterates in id() order, which varies with heap layout
        self._writers: dict[asyncio.StreamWriter, None] = {}

    async def spawn(self) -> None:
        try:
            self._server = await asyncio.start_server(
                self._handle_connection, self.host, self.port,
                backlog=ACCEPT_BACKLOG,
            )
        except OSError as e:
            from .errors import classify

            raise classify(e, "listen", (self.host, self.port)) from e
        log.debug("Listening on %s:%d", self.host, self.port)

    async def _handle_connection(
        self, reader: asyncio.StreamReader, stream_writer: asyncio.StreamWriter
    ) -> None:
        peer = stream_writer.get_extra_info("peername")
        set_nodelay(stream_writer)
        log.debug("Incoming connection from %s", peer)
        self._writers[stream_writer] = None
        writer = Writer(stream_writer, flows=self._flows, node=self._node)
        try:
            while True:
                frame = await read_frame(reader)
                # charged before the inbound cut: the bytes crossed the
                # wire whether or not the isolate window swallows them
                if self._flows is not None:
                    self._flows.rx(peer, frame)
                if self._faults is not None and self._faults.inbound_cut():
                    continue  # isolate window: swallow the frame unACKed
                await dispatch_ingest(self.handler, writer, frame)
        except (
            asyncio.IncompleteReadError,
            ConnectionError,
            OSError,
            FramingError,
        ):
            log.debug("Connection from %s closed", peer)
        finally:
            self._writers.pop(stream_writer, None)
            stream_writer.close()

    @property
    def connections(self) -> int:
        """Live accepted connections (ingest_connections gauge)."""
        return len(self._writers)

    async def shutdown(self) -> None:
        if self._server is not None:
            self._server.close()
            # Persistent peers hold their connections open; close them so
            # wait_closed() (which in 3.12 waits on every live connection)
            # can complete.
            for w in list(self._writers):
                w.close()
            await self._server.wait_closed()
            self._server = None
