"""Shared consensus test fixtures.

Mirrors the reference's fixture strategy (consensus/src/tests/common.rs:
17-198): a deterministic 4-node committee from a fixed seed, synchronous
signing constructors, a valid-chain builder, and raw-TCP listener tasks
standing in for remote peers.
"""

from __future__ import annotations

import asyncio
import functools
import itertools
import os

from hotstuff_tpu.consensus import QC, TC, Block, Committee, Timeout, Vote
from hotstuff_tpu.consensus.synchronizer import ANCESTOR_COUNTS
from hotstuff_tpu.crypto import Digest, PublicKey, SecretKey, Signature, generate_keypair
from hotstuff_tpu.network.framing import read_frame, send_frame

SEED = bytes(32)

# unique port ranges per test to avoid clashes (common.rs:39-46).  An
# xdist worker is a process with a counter of its own, so each worker
# has a thousand ports of its own (gw0 from 26,000, gw5 to 31,999, all
# below the ephemeral range) and goes round inside them: no count of
# calls walks one worker into another's range.
_worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")[2:]
_first_port = 26_000 + 1_000 * (int(_worker) if _worker.isdigit() else 0)
_port_counter = itertools.cycle(range(_first_port, _first_port + 1_000, 20))


def fresh_base_port() -> int:
    return next(_port_counter)


def ancestor_lookups() -> tuple[int, int]:
    """(hits, misses) of the process's parent lookups so far: a test
    reads it before and after, since the count is the process's."""
    return ANCESTOR_COUNTS.hits, ANCESTOR_COUNTS.misses


def async_test(fn):
    """Run an async test function to completion on a fresh event loop
    (the image has no pytest-asyncio)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return asyncio.run(fn(*args, **kwargs))

    return wrapper


def keys(n: int = 4) -> list[tuple[PublicKey, SecretKey]]:
    """Deterministic committee keypairs, ordered by public key (so index i
    is also the round-robin leader of round r when r % n == i)."""
    pairs = [generate_keypair(SEED, i) for i in range(n)]
    pairs.sort(key=lambda kp: kp[0])
    return pairs


def committee(base_port: int, n: int = 4) -> Committee:
    return Committee.new(
        [
            (pk, 1, ("127.0.0.1", base_port + i))
            for i, (pk, _) in enumerate(keys(n))
        ]
    )


def secret_for(pk: PublicKey, n: int = 4) -> SecretKey:
    for cand, sk in keys(n):
        if cand == pk:
            return sk
    raise KeyError(pk)


def signed_block(
    author: PublicKey,
    secret: SecretKey,
    round_: int,
    qc: QC | None = None,
    tc: TC | None = None,
    payload: Digest | None = None,
) -> Block:
    block = Block(
        qc=qc if qc is not None else QC.genesis(),
        tc=tc,
        author=author,
        round=round_,
        payloads=(payload,) if payload is not None else (),
    )
    block.signature = Signature.new(block.digest(), secret)
    return block


def signed_vote(block: Block, author: PublicKey, secret: SecretKey) -> Vote:
    vote = Vote.for_block(block, author)
    vote.signature = Signature.new(vote.digest(), secret)
    return vote


def signed_timeout(
    high_qc: QC, round_: int, author: PublicKey, secret: SecretKey
) -> Timeout:
    timeout = Timeout(high_qc=high_qc, round=round_, author=author)
    timeout.signature = Signature.new(timeout.digest(), secret)
    return timeout


def qc_for_block(block: Block, n: int = 4, voters: int = 3) -> QC:
    """A valid QC over ``block`` signed by the first ``voters`` authorities
    (3 of 4 = quorum)."""
    vote_digest = Vote.for_block(block, keys(n)[0][0]).digest()
    return QC(
        hash=block.digest(),
        round=block.round,
        votes=[
            (pk, Signature.new(vote_digest, sk)) for pk, sk in keys(n)[:voters]
        ],
    )


def chain(length: int, n: int = 4) -> list[Block]:
    """A valid block chain b1..b_length with full QCs, each block authored
    by its round's round-robin leader (common.rs:147-179)."""
    pairs = keys(n)
    blocks: list[Block] = []
    qc = QC.genesis()
    for round_ in range(1, length + 1):
        author, secret = pairs[round_ % n]
        block = signed_block(
            author, secret, round_, qc=qc, payload=Digest.random()
        )
        blocks.append(block)
        qc = qc_for_block(block, n)
    return blocks


async def listener(
    port: int, expected: bytes | None = None, reply: bytes = b"Ack"
) -> bytes:
    """Bind a socket, accept one connection, return the first frame
    (optionally asserting its contents), reply with an ACK
    (common.rs:182-198)."""
    received: asyncio.Future[bytes] = asyncio.get_running_loop().create_future()

    async def handle(reader, writer):
        try:
            frame = await read_frame(reader)
            await send_frame(writer, reply)
            if not received.done():
                received.set_result(frame)
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", port)
    try:
        frame = await received
    finally:
        # NOTE: no wait_closed() — in 3.12 it blocks until every accepted
        # connection closes, and persistent senders hold theirs open.
        server.close()
    if expected is not None:
        assert frame == expected, "listener received unexpected frame"
    return frame
