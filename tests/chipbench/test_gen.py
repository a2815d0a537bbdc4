"""The generator's schedule: a function of the traffic file and the
seed, nothing sent early, lateness known, one home a payload."""

import asyncio
import base64
import hashlib
import struct
import time

from chipbench.gen import Generator, Plan, make_body

TRAFFIC = {
    "rate_tx_s": 200, "payload_bytes": 512, "ramp_s": 0.5,
    "drain_cap_s": 0.5,
}
SEED = 2_147_483_900  # more than 32 signed bits hold


def test_plan_is_a_function_of_the_seed():
    a, b = Plan(TRAFFIC, 4, SEED, 1.0), Plan(TRAFFIC, 4, SEED, 1.0)
    other = Plan(TRAFFIC, 4, SEED + 1, 1.0)
    assert a.frames == b.frames and a.ids == b.ids and a.offset == b.offset
    assert a.ids != other.ids
    assert len(set(a.ids)) == a.count == 400  # (0.5 + 1 + 0.5) s at 200 tx/s
    assert make_body(SEED, 7, 512)[:8] == (7).to_bytes(8, "big")
    assert len(make_body(SEED, 7, 512)) == 512


def test_schedule_homes_and_window():
    plan = Plan(TRAFFIC, 4, SEED, 1.0)
    assert plan.due_s[:3] == [0.0, 1 / 200, 2 / 200]
    assert all(
        plan.home[k] == (plan.offset + k) % 4 for k in range(plan.count)
    )
    assert plan.window() == range(100, 300)
    # every seed sends the same sizes at the same times: only the
    # bodies and the first home differ
    other = Plan(TRAFFIC, 4, SEED + 1, 1.0)
    assert other.due_s == plan.due_s
    assert {len(f) for f in plan.frames} == {len(other.frames[0])}


def test_frame_is_the_programs_producer_frame():
    plan = Plan(TRAFFIC, 4, SEED, 1.0)
    frame = plan.frames[5]
    (length,) = struct.unpack(">I", frame[:4])
    assert length == len(frame) - 4 == 1 + 32 + 4 + 512
    assert frame[4] == 5
    digest, body = frame[5:37], frame[41:]
    assert struct.unpack("<I", frame[37:41]) == (512,)
    assert digest == hashlib.sha512(body).digest()[:32]
    assert plan.ids[5] == base64.b64encode(digest).decode()[:16]
    from hotstuff_tpu.consensus.wire import encode_producer
    from hotstuff_tpu.crypto import Digest

    assert frame[4:] == encode_producer(Digest(digest), body)
    assert str(Digest(digest)) == plan.ids[5]


def test_nothing_is_sent_early_and_busy_counts_as_refused():
    """Against a stand-in node that takes frames, answers each with Ack
    and the third with the program's typed BUSY frame."""
    from hotstuff_tpu.consensus.wire import encode_ingest_ack

    plan = Plan(TRAFFIC, 2, SEED, 1.0)
    arrivals: list[tuple[float, bytes]] = []

    async def node(reader, writer):
        seen = 0
        try:
            while True:
                (length,) = struct.unpack(">I", await reader.readexactly(4))
                message = await reader.readexactly(length)
                arrivals.append((time.time(), message[1:33]))
                seen += 1
                reply = (
                    encode_ingest_ack(0, 1, 0, 10) if seen == 3 else b"Ack"
                )
                writer.write(struct.pack(">I", len(reply)) + reply)
        except (asyncio.IncompleteReadError, ConnectionError):
            writer.close()

    async def scenario():
        servers = [
            await asyncio.start_server(node, "127.0.0.1", 0) for _ in range(2)
        ]
        addresses = [s.sockets[0].getsockname()[:2] for s in servers]
        gen = Generator(addresses, plan)
        await gen.connect(time.time() + 5)
        t_ramp = time.time() + 0.05
        await asyncio.wait_for(gen.run(t_ramp, asyncio.Event()), 10)
        await asyncio.sleep(0.1)
        gen.close()
        for s in servers:
            s.close()
        return gen, t_ramp

    gen, t_ramp = asyncio.run(scenario())
    assert gen.next_k == plan.count and len(arrivals) == plan.count
    late = [gen.sent_at[k] - (t_ramp + plan.due_s[k]) for k in range(plan.count)]
    assert min(late) >= 0.0  # never before it is due
    assert sorted(late)[len(late) // 2] < 0.05  # and the lateness is known
    # one BUSY a connection, each for the third frame that node got
    assert sorted(gen.refused) == [4, 5]
