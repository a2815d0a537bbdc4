"""The one traffic generator: an open loop on a fixed schedule.

Payload ``k`` is due at ``t_ramp + k / rate``, goes to node
``(offset + k) mod n`` (one home) and is never sent before it is due.
Bodies and ``offset`` come from the seed.  The framing is the program's
producer path as ``hotstuff_tpu/node/client.py`` speaks it (a u32
big-endian length, then tag 5, the 32-byte digest, a u32 little-endian
body length, the body); pacing and sampling are not that client's: it
sends 20 bursts a second up to 50 ms before they are due and logs one
sample a burst.
"""

from __future__ import annotations

import asyncio
import base64
import hashlib
import random
import socket
import struct
import time

#: the loop wakes at the next due time, and at most this often
TICK_S = 0.002
TAG_PRODUCER = 5
INGEST_ACK_TAG = 0xA2
INGEST_BUSY = 1
#: priming payloads (one a node, before the schedule starts) count from
#: here, so that no body of theirs equals a scheduled one
PRIME_BASE = 1 << 62


def make_body(seed: int, k: int, size: int) -> bytes:
    """Body of payload ``k``: its counter, then bytes drawn from the
    seed and the counter (the program's client tags bodies the same
    way, so that every body, and so every digest, is distinct)."""
    fill = random.Random(f"{seed}:{k}").randbytes(max(0, size - 8))
    return k.to_bytes(8, "big") + fill


def digest_of(body: bytes) -> bytes:
    """The program's content address: SHA-512 cut to 32 bytes."""
    return hashlib.sha512(body).digest()[:32]


def digest_id(digest: bytes) -> str:
    """A digest as the program's log shows it: 16 base64 characters."""
    return base64.b64encode(digest).decode()[:16]


def producer_frame(digest: bytes, body: bytes) -> bytes:
    message = (
        bytes([TAG_PRODUCER]) + digest + struct.pack("<I", len(body)) + body
    )
    return struct.pack(">I", len(message)) + message


class Plan:
    """What one run sends: for payload ``k`` its offset from the start
    of the ramp (``due_s``), its home node, its digest as the log shows
    it, and its frame.  A function of the traffic file and the seed."""

    def __init__(self, traffic: dict, nodes: int, seed: int, seconds: float):
        rng = random.Random(seed)
        self.rate = float(traffic["rate_tx_s"])
        self.size = int(traffic["payload_bytes"])
        self.ramp_s = float(traffic["ramp_s"])
        self.seconds = float(seconds)
        self.nodes = nodes
        self.offset = rng.randrange(nodes)
        # the schedule goes on through the drain, so that the last
        # payloads of the window see the load the first ones saw
        self.after_s = float(traffic["drain_cap_s"])
        horizon = self.ramp_s + self.seconds + self.after_s
        self.count = int(horizon * self.rate)
        self.first = int(round(self.ramp_s * self.rate))
        self.last = int(round((self.ramp_s + self.seconds) * self.rate))
        self.due_s = [k / self.rate for k in range(self.count)]
        self.home = [(self.offset + k) % nodes for k in range(self.count)]
        self.ids: list[str] = []
        self.frames: list[bytes] = []
        for k in range(self.count):
            body = make_body(seed, k, self.size)
            digest = digest_of(body)
            self.ids.append(digest_id(digest))
            self.frames.append(producer_frame(digest, body))
        self.prime_frames: list[bytes] = []
        for i in range(nodes):
            body = make_body(seed, PRIME_BASE + i, self.size)
            self.prime_frames.append(producer_frame(digest_of(body), body))

    def window(self) -> range:
        """The payloads that are due inside the measured window."""
        return range(self.first, self.last)


class _Conn:
    """One persistent connection to a node.  Every frame gets one reply
    in order (``b"Ack"``, or a typed BUSY frame when the node's
    admission sheds it), so the k-th reply speaks of the k-th frame."""

    def __init__(self, address):
        self.address = address
        self.writer: asyncio.StreamWriter | None = None
        self.reader_task: asyncio.Task | None = None
        self.in_flight: list[int] = []
        self.replies = 0
        self.alive = False

    async def open(self, on_refused) -> None:
        reader, self.writer = await asyncio.open_connection(*self.address)
        sock = self.writer.get_extra_info("socket")
        if sock is not None:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.alive = True
        self.reader_task = asyncio.ensure_future(self._read(reader, on_refused))

    async def _read(self, reader, on_refused) -> None:
        try:
            while True:
                (length,) = struct.unpack(">I", await reader.readexactly(4))
                reply = await reader.readexactly(length)
                k = self.in_flight[self.replies]
                self.replies += 1
                if (
                    len(reply) >= 3
                    and reply[0] == INGEST_ACK_TAG
                    and reply[2] == INGEST_BUSY
                ):
                    on_refused(k)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            self.alive = False

    def close(self) -> None:
        if self.reader_task is not None:
            self.reader_task.cancel()
        if self.writer is not None:
            self.writer.close()


class Generator:
    """Sends a ``Plan`` to the committee and keeps, for every payload,
    when it was sent and whether the node refused it."""

    def __init__(self, addresses, plan: Plan):
        self.plan = plan
        self.conns = [_Conn(a) for a in addresses]
        self.sent_at: list[float | None] = [None] * plan.count
        self.refused: set[int] = set()
        self.next_k = 0

    async def connect(self, deadline: float, alive=lambda: True) -> None:
        """Open one connection to every node, trying until ``deadline``
        (the committee binds its ports only after the warm-up)."""
        for conn in self.conns:
            while not conn.alive:
                try:
                    await conn.open(self.refused.add)
                except OSError:
                    if time.time() > deadline or not alive():
                        raise
                    await asyncio.sleep(0.1)

    def prime(self) -> None:
        """One payload to every node: a leader with nothing to propose
        defers its block, and the committee has to commit one before the
        set-up counts as done."""
        for conn, frame in zip(self.conns, self.plan.prime_frames):
            conn.in_flight.append(-1)
            conn.writer.write(frame)

    async def run(self, t_ramp: float, stop: asyncio.Event) -> None:
        """Send each payload at its due time, or at the first tick
        after it, until ``stop`` is set or the plan is through."""
        plan = self.plan
        while self.next_k < plan.count and not stop.is_set():
            now = time.time()
            wait = t_ramp + plan.due_s[self.next_k] - now
            if wait > 0:
                await asyncio.sleep(max(wait, TICK_S))
                continue
            touched = set()
            while (
                self.next_k < plan.count
                and t_ramp + plan.due_s[self.next_k] <= now
            ):
                k = self.next_k
                self.next_k += 1
                conn = self.conns[plan.home[k]]
                if not conn.alive:
                    self.refused.add(k)
                    continue
                conn.in_flight.append(k)
                conn.writer.write(plan.frames[k])
                self.sent_at[k] = now
                touched.add(conn)
            for conn in touched:
                try:
                    await conn.writer.drain()
                except (ConnectionError, OSError):
                    conn.alive = False
            await asyncio.sleep(TICK_S)

    def close(self) -> None:
        for conn in self.conns:
            conn.close()
