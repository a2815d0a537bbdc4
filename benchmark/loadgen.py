"""Open-loop client-fleet load generator for the ingest plane.

The fixed-burst benchmark client (``hotstuff_tpu/node/client.py``) sends
a constant quantum 20 times a second and measures whatever commits; it
never observes the admission controller (docs/LOAD.md) because it speaks
producer frame v1 and discards every reply.  This module is the other
half of the ingest plane:

- ``run_load`` — an asyncio fleet modeling K virtual clients whose
  aggregate Poisson arrival process (seeded, exponential inter-arrival
  times) is multiplexed over M connections per node.  Arrival-driven,
  never ping-pong: an arrival that cannot be submitted right now (every
  connection out of credit or in a BUSY backoff window) is counted as
  client-side shed and dropped, NOT queued — queuing would turn the
  open loop into a closed one and hide saturation.
- credit honoring: payloads ride producer frame v2 batches
  (``encode_producer_batch``) and every typed ingest ACK resets the
  connection's credit window; a BUSY ACK additionally pauses the
  connection for the node's ``retry_after_ms`` hint.
- ``LoadBench`` — the LocalBench harness with the fleet as the client
  process and telemetry forced on, so the ``ingest`` section of each
  node's snapshot is scrapeable after the run.
- ``run_sweep`` — saturation-sweep mode: walk the offered rate upward
  (doubling) until goodput stops improving, then drive 2x the measured
  saturation rate against a deliberately small proposer buffer and
  check the backpressure invariant: sheds observed, zero silent
  drop-newest.

Latency attribution: every Nth payload is tagged with the same
``Sending sample payload <digest>`` contract line the fixed client
emits, so ``LogParser`` maps it to its committed block and
``end_to_end_latency_percentiles`` yields the client-observed p50/p99.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import random
import re
import sys

log = logging.getLogger("loadgen")

#: scheduling quantum of the arrival loop (arrivals are timestamped by
#: the Poisson process, the tick only batches their submission)
TICK = 0.01
#: optimistic pre-first-ACK credit per connection — mirrors the
#: admission controller's MIN_CREDIT floor
INITIAL_CREDIT = 64
#: target sample-tag rate (samples/s) for latency attribution; the
#: contract line is log-scraped, so tagging every payload at high rates
#: would make the client log the bottleneck
SAMPLE_TARGET_PER_S = 200

# Machine-readable result line the harness scrapes from the client log
# (one JSON document; written LAST so a truncated log fails loudly).
RE_LOAD_STATS = re.compile(r"Load stats: (\{.*\})")


class _LoadConn:
    """One credit-tracked framed connection to a node.

    The reply stream is PARSED (unlike the fixed client's discard-all
    sink): typed ingest ACKs reset the credit window and feed the
    accepted/shed counters; a legacy ``b"Ack"`` (v1 frames only) is
    ignored."""

    def __init__(self, address):
        self.address = address
        self.writer: asyncio.StreamWriter | None = None
        self._sink: asyncio.Task | None = None
        self.alive = False
        self.credit = INITIAL_CREDIT
        self.busy_until = 0.0
        self.accepted = 0
        self.shed = 0
        self.busy_frames = 0

    async def connect(self) -> None:
        from hotstuff_tpu.network.framing import set_nodelay

        reader, writer = await asyncio.open_connection(*self.address)
        try:
            set_nodelay(writer)
            sink = asyncio.ensure_future(self._read_acks(reader))
        except BaseException:
            writer.close()
            raise
        self.writer = writer
        self._sink = sink
        self.alive = True
        self.credit = INITIAL_CREDIT
        self.busy_until = 0.0

    def send_batch(self, frame: bytes, count: int) -> None:
        from hotstuff_tpu.network.framing import write_frame

        if not self.alive:
            return
        try:
            write_frame(self.writer, frame)
            self.credit -= count
        except (ConnectionError, OSError):
            self.mark_dead()

    async def drain(self, timeout: float = 1.0) -> None:
        if not self.alive:
            return
        try:
            await asyncio.wait_for(self.writer.drain(), timeout)
        except (ConnectionError, OSError, asyncio.TimeoutError):
            self.mark_dead()

    async def _read_acks(self, reader: asyncio.StreamReader) -> None:
        from hotstuff_tpu.consensus.errors import SerializationError
        from hotstuff_tpu.consensus.wire import decode_ingest_ack
        from hotstuff_tpu.network.framing import read_frame

        loop = asyncio.get_running_loop()
        try:
            while True:
                frame = await read_frame(reader)
                try:
                    ack = decode_ingest_ack(frame)
                except SerializationError:
                    continue
                if ack is None:
                    continue  # legacy v1 Ack
                self.accepted += ack.accepted
                self.shed += ack.shed
                # the ACK's credit is the node's CURRENT window — an
                # authoritative reset, not an increment
                self.credit = ack.credit
                if ack.busy:
                    self.busy_frames += 1
                    self.busy_until = max(
                        self.busy_until,
                        loop.time() + ack.retry_after_ms / 1e3,
                    )
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            self.mark_dead()

    def mark_dead(self) -> None:
        if self.alive:
            log.warning(
                "Node %s:%d unreachable; dropping until it returns",
                *self.address,
            )
        self.alive = False
        self.close()

    def close(self) -> None:
        if self._sink is not None:
            self._sink.cancel()
            self._sink = None
        if self.writer is not None:
            self.writer.close()
            self.writer = None


class _ReadConn:
    """One framed connection issuing QC-anchored ledger reads
    (``TAG_STATE_READ``) against a node's replicated execution layer.

    Reads are NOT admission-controlled (the node answers at its last
    applied version without touching the ingest plane), so there is no
    credit window — just a FIFO of send timestamps matched to the
    in-order reply stream for round-trip latency."""

    def __init__(self, address):
        self.address = address
        self.writer: asyncio.StreamWriter | None = None
        self._sink: asyncio.Task | None = None
        self.alive = False
        self.sent = 0
        self.replies = 0
        self.found = 0
        self.version_max = 0
        self.latencies: list[float] = []
        self._pending: list[float] = []  # FIFO of send times

    async def connect(self) -> None:
        from hotstuff_tpu.network.framing import set_nodelay

        reader, writer = await asyncio.open_connection(*self.address)
        try:
            set_nodelay(writer)
            sink = asyncio.ensure_future(self._read_replies(reader))
        except BaseException:
            writer.close()
            raise
        self.writer = writer
        self._sink = sink
        self.alive = True
        self._pending.clear()

    def send_read(self, frame: bytes) -> None:
        from hotstuff_tpu.network.framing import write_frame

        if not self.alive:
            return
        try:
            write_frame(self.writer, frame)
        except (ConnectionError, OSError):
            self.mark_dead()
            return
        self.sent += 1
        self._pending.append(asyncio.get_running_loop().time())

    async def _read_replies(self, reader: asyncio.StreamReader) -> None:
        from hotstuff_tpu.consensus.wire import decode_state_value
        from hotstuff_tpu.network.framing import read_frame

        loop = asyncio.get_running_loop()
        try:
            while True:
                frame = await read_frame(reader)
                sv = decode_state_value(frame)
                if sv is None:
                    continue
                self.replies += 1
                if self._pending:
                    lat = loop.time() - self._pending.pop(0)
                    if len(self.latencies) < 10_000:
                        self.latencies.append(lat)
                if sv.found:
                    self.found += 1
                self.version_max = max(self.version_max, sv.state_version)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            self.mark_dead()

    def mark_dead(self) -> None:
        self.alive = False
        self.close()

    def close(self) -> None:
        if self._sink is not None:
            self._sink.cancel()
            self._sink = None
        if self.writer is not None:
            self.writer.close()
            self.writer = None


async def run_load(
    addresses,
    rate: int,
    duration: float,
    clients: int = 64,
    conns_per_node: int = 2,
    size: int = 512,
    seed: int = 1,
    warmup: float = 0.0,
    expect_faults: int = 0,
    read_fraction: float = 0.0,
) -> dict:
    """Drive a Poisson arrival process at ``rate`` tx/s for ``duration``
    seconds over ``conns_per_node`` connections to each node, honoring
    per-connection admission credits.  With ``read_fraction > 0`` each
    arrival is a LEDGER READ with that probability instead of a write:
    a ``TAG_STATE_READ`` round-trip against a recently written payload
    digest, answered at the node's last applied state version (a
    lagging node serves a QC-anchored stale read — the miss/hit split
    and the version spread are the measurement).  Returns the stats
    dict that is also written to the log as the ``Load stats:``
    contract line."""
    from hotstuff_tpu.consensus.wire import (
        MAX_PRODUCER_BATCH,
        STATE_READ_LEDGER,
        encode_producer_batch,
        encode_state_read,
    )
    from hotstuff_tpu.crypto import Digest
    from hotstuff_tpu.node.client import wait_for_nodes

    log.info("Waiting for all nodes to be online...")
    boot_timeout = max(15.0, 3.0 * len(addresses))
    live_addrs = await wait_for_nodes(
        addresses, timeout=boot_timeout, expect_faults=expect_faults
    )
    if not live_addrs:
        log.error("No nodes reachable")
        return {}
    if warmup:
        await asyncio.sleep(warmup)

    conns = [
        _LoadConn(a) for a in live_addrs for _ in range(conns_per_node)
    ]
    # one dedicated read connection per node — read replies must not
    # interleave with the write plane's credit-bearing ingest ACKs
    read_conns = (
        [_ReadConn(a) for a in live_addrs] if read_fraction > 0 else []
    )
    for c in conns + read_conns:
        try:
            await asyncio.wait_for(c.connect(), 2.0)
        except (OSError, asyncio.TimeoutError):
            log.warning(
                "Node %s:%d refused the connection; will retry", *c.address
            )

    async def reconnector() -> None:
        while True:
            await asyncio.sleep(2.0)
            for c in conns + read_conns:
                if not c.alive:
                    try:
                        await asyncio.wait_for(c.connect(), 1.5)
                        log.info("Reconnected to %s:%d", *c.address)
                    except (OSError, asyncio.TimeoutError):
                        pass

    reconnect_task = asyncio.ensure_future(reconnector())

    rng = random.Random(seed)
    sample_every = max(1, rate // SAMPLE_TARGET_PER_S)
    log.info("Start sending transactions")
    # NOTE: these log entries are used to compute performance.
    log.info("Transactions rate: %d tx/s", rate)
    log.info("Transactions size: %d B", size)
    log.info(
        "Virtual clients: %d over %d connection(s)",
        clients,
        len(conns),
    )
    if read_fraction > 0:
        log.info("Read fraction: %.2f", read_fraction)

    loop = asyncio.get_running_loop()
    start = loop.time()
    next_arrival = start + rng.expovariate(rate)
    offered = submitted = client_shed = counter = 0
    rr = 0  # connection rotation cursor across ticks
    reads_offered = read_rr = 0
    recent: list = []  # recently written digests, the read working set
    try:
        while True:
            now = loop.time()
            if now - start >= duration:
                break
            # arrivals whose Poisson timestamp has passed are due NOW;
            # the duration bound applies to the timestamps so the
            # offered count matches rate*duration in expectation
            due = 0
            while next_arrival <= now and next_arrival - start < duration:
                due += 1
                next_arrival += rng.expovariate(rate)
            # a read needs a working set — until the first write lands,
            # every arrival stays a write
            if due and read_conns and recent:
                reads_due = sum(
                    1 for _ in range(due) if rng.random() < read_fraction
                )
                due -= reads_due
                reads_offered += reads_due
                live_readers = [r for r in read_conns if r.alive]
                for _ in range(reads_due):
                    if not live_readers:
                        break
                    target = live_readers[read_rr % len(live_readers)]
                    read_rr += 1
                    digest = recent[rng.randrange(len(recent))]
                    target.send_read(
                        encode_state_read(STATE_READ_LEDGER, digest)
                    )
            if due:
                offered += due
                eligible = [
                    c
                    for c in conns
                    if c.alive and c.credit > 0 and now >= c.busy_until
                ]
                # round-robin the due arrivals over the eligible
                # connections (rotated each tick so no node is first
                # forever), bounded by each one's remaining credit —
                # whatever cannot be placed is open-loop client shed
                if eligible:
                    off = rr % len(eligible)
                    order = eligible[off:] + eligible[:off]
                    rr += 1
                else:
                    order = []
                budgets = [c.credit for c in order]
                batches: list[list] = [[] for _ in order]
                placed = k = misses = 0
                while placed < due and order:
                    i = k % len(order)
                    k += 1
                    if budgets[i] <= 0:
                        misses += 1
                        if misses >= len(order):
                            break  # every connection out of credit
                        continue
                    misses = 0
                    body = counter.to_bytes(8, "big") + os.urandom(
                        max(0, size - 8)
                    )
                    digest = Digest.of(body)
                    if counter % sample_every == 0:
                        # NOTE: used to compute performance.
                        log.info("Sending sample payload %s", digest)
                    batches[i].append((digest, body))
                    if read_conns:
                        recent.append(digest.to_bytes())
                        if len(recent) > 1024:
                            del recent[:512]
                    budgets[i] -= 1
                    counter += 1
                    placed += 1
                client_shed += due - placed
                for i, c in enumerate(order):
                    for lo in range(0, len(batches[i]), MAX_PRODUCER_BATCH):
                        chunk = batches[i][lo : lo + MAX_PRODUCER_BATCH]
                        c.send_batch(
                            encode_producer_batch(chunk), len(chunk)
                        )
                        submitted += len(chunk)
                for i, c in enumerate(order):
                    if batches[i]:
                        await c.drain()
            await asyncio.sleep(
                max(0.0, min(TICK, next_arrival - loop.time()))
            )
    finally:
        reconnect_task.cancel()
        # reads in flight when the window closes would miss their
        # replies — give the in-order streams a moment to drain
        if read_conns and any(r._pending for r in read_conns):
            await asyncio.sleep(0.25)
        for c in conns + read_conns:
            c.close()

    window = loop.time() - start
    stats = {
        "rate": rate,
        "clients": clients,
        "connections": len(conns),
        "window_s": round(window, 2),
        "offered": offered,
        "submitted": submitted,
        "accepted": sum(c.accepted for c in conns),
        "shed_server": sum(c.shed for c in conns),
        "shed_client": client_shed,
        "busy_frames": sum(c.busy_frames for c in conns),
    }
    if read_conns:
        lat = sorted(
            x for r in read_conns for x in r.latencies
        )
        stats["reads"] = {
            "fraction": read_fraction,
            "offered": reads_offered,
            "sent": sum(r.sent for r in read_conns),
            "replies": sum(r.replies for r in read_conns),
            "found": sum(r.found for r in read_conns),
            "version_max": max(
                (r.version_max for r in read_conns), default=0
            ),
            "p50_ms": (
                round(lat[len(lat) // 2] * 1e3, 2) if lat else None
            ),
        }
    # NOTE: this log entry is used to compute performance.
    log.info("Load stats: %s", json.dumps(stats))
    return stats


# ---- harness side -----------------------------------------------------------


def scrape_load_stats(client_log: str) -> dict:
    """The fleet's ``Load stats:`` document from a client log, or {}."""
    matches = RE_LOAD_STATS.findall(client_log)
    if not matches:
        return {}
    try:
        return json.loads(matches[-1])
    except ValueError:
        return {}


def scrape_ingest(telemetry_docs) -> dict:
    """Committee-wide ingest counters summed over the per-node
    telemetry snapshots (the ``ingest`` section each node exports)."""
    out = {
        "accepted_total": 0,
        "shed_total": 0,
        "busy_frames": 0,
        "drop_newest": 0,
    }
    seen = False
    for doc in telemetry_docs:
        section = doc.get("ingest")
        if not isinstance(section, dict):
            continue
        seen = True
        for key in out:
            out[key] += int(section.get(key, 0) or 0)
    out["present"] = seen
    return out


class LoadBench:
    """One committee run with the credit-aware fleet as the client.

    Composition over the LocalBench subclass hook: builds a LocalBench,
    swaps its ``_client_cmd`` for the fleet's, forces telemetry on in
    every node (the ``ingest`` snapshot section is the measurement),
    and optionally pins the proposer buffer cap so short runs can
    actually reach the shed watermark."""

    def __init__(
        self,
        nodes: int = 4,
        rate: int = 1_000,
        duration: float = 10.0,
        clients: int = 64,
        conns_per_node: int = 2,
        tx_size: int = 512,
        seed: int = 1,
        max_pending: int | None = None,
        timeout_delay: int = 5_000,
        verifier: str = "cpu",
        base_port: int | None = None,
        read_fraction: float = 0.0,
    ):
        from .local import LocalBench

        kwargs = dict(
            nodes=nodes,
            rate=rate,
            duration=duration,
            timeout_delay=timeout_delay,
            verifier=verifier,
            tx_size=tx_size,
        )
        if base_port is not None:
            kwargs["base_port"] = base_port
        self.bench = LocalBench(**kwargs)
        self.clients = clients
        self.conns_per_node = conns_per_node
        self.seed = seed
        self.read_fraction = read_fraction
        self.bench.extra_env["HOTSTUFF_TELEMETRY"] = "1"
        if max_pending is not None:
            self.bench.extra_env["HOTSTUFF_MAX_PENDING"] = str(max_pending)
        self.bench._client_cmd = self._client_cmd  # the hook

    def _client_cmd(self, py: str) -> list[str]:
        from .utils import PathMaker

        b = self.bench
        return [
            py,
            "-m",
            "benchmark.loadgen",
            "--committee",
            PathMaker.committee_file(),
            "--rate",
            str(b.rate),
            "--duration",
            str(b.duration),
            "--size",
            str(b.tx_size),
            "--clients",
            str(self.clients),
            "--conns",
            str(self.conns_per_node),
            "--seed",
            str(self.seed),
            "--warmup",
            "2",
            "--faults",
            str(b.faults),
            "--read-fraction",
            str(self.read_fraction),
        ]

    def run(self) -> dict:
        """Run the committee and return one sweep row:
        offered/goodput/shed/latency plus the committee ingest
        counters."""
        import glob

        from .utils import PathMaker

        parser = self.bench.run()
        client_log = ""
        for path in sorted(
            glob.glob(os.path.join(PathMaker.logs_path(), "client*.log"))
        ):
            with open(path) as f:
                client_log += f.read()
        fleet = scrape_load_stats(client_log)
        ingest = scrape_ingest(parser.telemetry_docs)
        goodput, _window = parser.consensus_throughput()
        pcts = parser.end_to_end_latency_percentiles()
        return {
            "offered_tx_s": self.bench.rate,
            "goodput_tx_s": round(goodput, 1),
            "delivered_tx_s": (
                round(fleet["submitted"] / fleet["window_s"], 1)
                if fleet.get("window_s")
                else None
            ),
            "client_p50_ms": (
                round(pcts[0] * 1e3, 1) if pcts is not None else None
            ),
            "client_p99_ms": (
                round(pcts[1] * 1e3, 1) if pcts is not None else None
            ),
            "shed_server": ingest["shed_total"],
            "shed_client": fleet.get("shed_client", 0),
            "busy_frames": ingest["busy_frames"],
            "drop_newest": ingest["drop_newest"],
            "telemetry_present": ingest["present"],
            "fleet": fleet,
            **(
                {"reads": fleet["reads"]} if fleet.get("reads") else {}
            ),
        }


def run_sweep(
    nodes: int = 4,
    start_rate: int = 500,
    duration: float = 10.0,
    max_steps: int = 6,
    clients: int = 64,
    conns_per_node: int = 2,
    tx_size: int = 512,
    seed: int = 1,
    overload_max_pending: int = 2_000,
    plateau_gain: float = 0.10,
    read_fraction: float = 0.0,
) -> dict:
    """Saturation sweep: double the offered rate until goodput improves
    by less than ``plateau_gain`` (or ``max_steps`` runs), then drive
    2x the saturation rate against a small proposer buffer
    (``overload_max_pending``) and record the backpressure verdict."""
    from .utils import Print

    rows: list[dict] = []
    rate = start_rate
    best = 0.0
    for step in range(max_steps):
        Print.info(f"load sweep step {step + 1}: {rate} tx/s offered")
        row = LoadBench(
            nodes=nodes,
            rate=rate,
            duration=duration,
            clients=clients,
            conns_per_node=conns_per_node,
            tx_size=tx_size,
            seed=seed,
            read_fraction=read_fraction,
        ).run()
        rows.append(row)
        goodput = row["goodput_tx_s"] or 0.0
        if step > 0 and goodput < best * (1.0 + plateau_gain):
            break
        best = max(best, goodput)
        rate *= 2

    # saturation = the offered rate of the best-goodput row (the
    # plateau's left edge, not the overshot last step)
    sat_row = max(rows, key=lambda r: r["goodput_tx_s"] or 0.0)
    saturation = sat_row["offered_tx_s"]

    overload_rate = 2 * saturation
    Print.info(
        f"overload step: {overload_rate} tx/s offered "
        f"(2x saturation, max-pending {overload_max_pending})"
    )
    overload = LoadBench(
        nodes=nodes,
        rate=overload_rate,
        duration=duration,
        clients=clients,
        conns_per_node=conns_per_node,
        tx_size=tx_size,
        seed=seed,
        max_pending=overload_max_pending,
        read_fraction=read_fraction,
    ).run()
    sheds = overload["shed_server"] + overload["shed_client"]
    overload["backpressure_held"] = (
        overload["drop_newest"] == 0 and sheds > 0
    )
    return {
        "nodes": nodes,
        "clients": clients,
        "conns_per_node": conns_per_node,
        "duration_s": duration,
        "rows": rows,
        "saturation_tx_s": saturation,
        "overload": overload,
        "goodput_tx_s": sat_row["goodput_tx_s"],
        "client_p50_ms": sat_row["client_p50_ms"],
        "client_p99_ms": sat_row["client_p99_ms"],
        **(
            {"reads": sat_row["reads"]} if sat_row.get("reads") else {}
        ),
    }


def format_load_block(result: dict) -> str:
    """The ``+ LOAD`` SUMMARY block for a sweep result."""

    def txt(v, unit=""):
        return f"{v}{unit}" if v is not None else "n/a"

    lines = [
        " + LOAD:",
        f" Committee size: {result['nodes']} node(s)",
        f" Virtual clients: {result['clients']} over"
        f" {result['conns_per_node']} connection(s)/node",
        f" Step duration: {result['duration_s']:.0f} s",
        f" Saturation: ~{result['saturation_tx_s']} tx/s offered"
        " (goodput plateau)",
        "",
        "  offered tx/s  goodput tx/s  shed/s  p50 ms  p99 ms",
    ]
    for row in result["rows"]:
        shed = row["shed_server"] + row["shed_client"]
        shed_s = round(shed / result["duration_s"], 1) if shed else 0
        lines.append(
            f"  {row['offered_tx_s']:>12}"
            f"  {txt(row['goodput_tx_s']):>12}"
            f"  {shed_s:>6}"
            f"  {txt(row['client_p50_ms']):>6}"
            f"  {txt(row['client_p99_ms']):>6}"
        )
    o = result["overload"]
    verdict = (
        "backpressure HELD (sheds observed, zero silent drop-newest)"
        if o["backpressure_held"]
        else "backpressure verdict: "
        + (
            f"drop_newest={o['drop_newest']} (silent drops!)"
            if o["drop_newest"]
            else "no sheds observed (offered rate below the watermark)"
        )
    )
    reads = result.get("reads")
    if reads:
        lines += [
            "",
            f" Mixed reads ({reads['fraction']:.0%} of arrivals):"
            f" {reads['sent']} sent, {reads['replies']} answered,"
            f" {reads['found']} found,"
            f" p50 {txt(reads['p50_ms'], ' ms')},"
            f" served at state version <= {reads['version_max']}",
        ]
    lines += [
        "",
        f" Overload (2x saturation = {o['offered_tx_s']} tx/s):",
        f" Goodput: {txt(o['goodput_tx_s'])} tx/s,"
        f" shed {o['shed_server']} (server) + {o['shed_client']} (client),"
        f" busy frames {o['busy_frames']}",
        f" Proposer drop-newest: {o['drop_newest']} — {verdict}",
    ]
    return "\n".join(lines) + "\n"


# ---- fleet CLI (the client process LoadBench spawns) ------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Credit-aware open-loop load-generator fleet"
    )
    parser.add_argument("--committee", required=True)
    parser.add_argument("--rate", type=int, default=1_000)
    parser.add_argument("--duration", type=float, default=10.0)
    parser.add_argument("--size", type=int, default=512)
    parser.add_argument("--clients", type=int, default=64)
    parser.add_argument(
        "--conns", type=int, default=2, help="connections per node"
    )
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--warmup", type=float, default=2.0)
    parser.add_argument("--faults", type=int, default=0)
    parser.add_argument(
        "--read-fraction",
        type=float,
        default=0.0,
        help="probability each arrival is a QC-anchored ledger read "
        "instead of a write (0 = pure write fleet)",
    )
    parser.add_argument("-v", "--verbose", action="count", default=1)
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=[logging.ERROR, logging.INFO, logging.DEBUG][
            min(args.verbose, 2)
        ],
        format="%(asctime)s.%(msecs)03dZ [%(levelname)s] %(message)s",
        datefmt="%Y-%m-%dT%H:%M:%S",
    )

    from hotstuff_tpu.consensus.wire import MAX_PAYLOAD_BODY
    from hotstuff_tpu.node.config import read_committee

    if not 8 <= args.size <= MAX_PAYLOAD_BODY:
        parser.error(
            f"--size must be in [8, {MAX_PAYLOAD_BODY}] (the 8-byte "
            "uniqueness counter rides every body)"
        )
    if args.rate < 1 or args.conns < 1 or args.clients < 1:
        parser.error("--rate, --conns and --clients must be >= 1")
    if not 0.0 <= args.read_fraction < 1.0:
        parser.error("--read-fraction must be in [0, 1)")
    committee = read_committee(args.committee)
    addresses = [a.address for a in committee.authorities.values()]
    asyncio.run(
        run_load(
            addresses,
            args.rate,
            args.duration,
            clients=args.clients,
            conns_per_node=args.conns,
            size=args.size,
            seed=args.seed,
            warmup=args.warmup,
            expect_faults=args.faults,
            read_fraction=args.read_fraction,
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
