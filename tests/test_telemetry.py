"""Telemetry subsystem tests (ISSUE 1).

Covers the tentpole pieces — bounded trace recorder, log-bucket
histograms, Prometheus /metrics golden output, the snapshot document's
snapshot scrape contract and the 'Host stats:' line, and a 4-node in-process run producing
a commit-latency breakdown — plus regressions for the satellite fixes
(fd-limit RLIM_INFINITY, gc gen2 knob, reliable-sender idle eviction,
broadcast pacing).
"""

import asyncio
import gc
import json
import os

import pytest

from hotstuff_tpu import telemetry
from hotstuff_tpu.telemetry.metrics import (
    LATENCY_BOUNDS_S,
    Histogram,
    Registry,
)
from hotstuff_tpu.telemetry.trace import TraceRecorder
from hotstuff_tpu.crypto.service import VerifyWork
from hotstuff_tpu.telemetry import SNAPSHOT_WORK_KEYS, hoststats

from .common import async_test, committee, fresh_base_port, keys


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    """Telemetry state is process-global: every test starts disabled
    with an empty registry and leaves it that way."""
    monkeypatch.delenv("HOTSTUFF_TELEMETRY", raising=False)
    monkeypatch.delenv("HOTSTUFF_METRICS_PORT", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


# ---- instruments --------------------------------------------------------


def test_histogram_bucketing():
    h = Histogram("lat", bounds=LATENCY_BOUNDS_S)
    h.observe(0.00005)  # below the first bound (100 us)
    h.observe(0.0003)  # bucket with bound 0.0004
    h.observe(1.0)
    h.observe(500.0)  # beyond the last bound -> overflow bucket
    assert h.count == 4
    assert h.counts[0] == 1
    assert h.counts[-1] == 1  # overflow
    assert h.max == 500.0
    j = h.to_json()
    assert j["count"] == 4
    assert j["max_ms"] == 500000.0
    # percentile is an upper-bound estimate: p50 of this set must be a
    # real bucket bound >= the true median
    assert h.percentile(0.5) in LATENCY_BOUNDS_S


def test_histogram_empty_snapshot():
    h = Histogram("lat")
    assert h.to_json() == {"count": 0}
    assert h.percentile(0.99) == 0.0


def test_registry_idempotent_and_labels():
    reg = Registry()
    a = reg.counter("foo", "help", {"node": "a"})
    again = reg.counter("foo", "other help ignored", {"node": "a"})
    other = reg.counter("foo", "", {"node": "b"})
    assert a is again
    assert a is not other
    a.inc(3)
    assert again.value == 3


def test_prometheus_golden_output():
    reg = Registry()
    c = reg.counter("commits", "Blocks committed", {"node": "n0"})
    c.inc(7)
    reg.gauge("depth", "Queue depth", {"node": "n0"}, fn=lambda: 4)
    h = reg.histogram("lat", "Latency", {"node": "n0"}, bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render_prometheus()
    expected = (
        "# HELP hotstuff_commits Blocks committed\n"
        "# TYPE hotstuff_commits counter\n"
        'hotstuff_commits{node="n0"} 7\n'
        "# HELP hotstuff_depth Queue depth\n"
        "# TYPE hotstuff_depth gauge\n"
        'hotstuff_depth{node="n0"} 4\n'
        "# HELP hotstuff_lat Latency\n"
        "# TYPE hotstuff_lat histogram\n"
        'hotstuff_lat_bucket{node="n0",le="0.1"} 1\n'
        'hotstuff_lat_bucket{node="n0",le="1"} 1\n'
        'hotstuff_lat_bucket{node="n0",le="+Inf"} 2\n'
        'hotstuff_lat_sum{node="n0"} 5.05\n'
        'hotstuff_lat_count{node="n0"} 2\n'
    )
    assert text == expected


def test_openmetrics_golden_output():
    """OpenMetrics 1.0 exposition: counter FAMILY names drop _total in
    metadata while counter SAMPLES carry it (no double suffix for
    instruments already named *_total), and the body ends in # EOF."""
    reg = Registry()
    reg.counter("commits", "Blocks committed", {"node": "n0"}).inc(7)
    reg.counter("requests_total", "Requests", {"node": "n0"}).inc(2)
    reg.gauge("depth", "Queue depth", {"node": "n0"}, fn=lambda: 4)
    text = reg.render_openmetrics()
    expected = (
        "# HELP hotstuff_commits Blocks committed\n"
        "# TYPE hotstuff_commits counter\n"
        'hotstuff_commits_total{node="n0"} 7\n'
        "# HELP hotstuff_requests Requests\n"
        "# TYPE hotstuff_requests counter\n"
        'hotstuff_requests_total{node="n0"} 2\n'
        "# HELP hotstuff_depth Queue depth\n"
        "# TYPE hotstuff_depth gauge\n"
        'hotstuff_depth{node="n0"} 4\n'
        "# EOF\n"
    )
    assert text == expected


def test_gauge_callback_failure_is_sentinel():
    reg = Registry()
    g = reg.gauge("bad", fn=lambda: 1 / 0)
    assert g.value == -1.0  # a scrape must never throw


# ---- trace recorder -----------------------------------------------------


def test_trace_open_records_bounded():
    reg = Registry()
    tr = TraceRecorder(reg, capacity=8, ring=4)
    for i in range(100):
        tr.mark_proposed(i.to_bytes(32, "big"), i)
    assert tr.open_count() == 8  # FIFO eviction at capacity


def test_trace_ring_bounded_and_edges():
    t = [0.0]

    def clock():
        t[0] += 0.010
        return t[0]

    reg = Registry()
    tr = TraceRecorder(reg, ring=4, clock=clock)
    for i in range(10):
        d = i.to_bytes(32, "big")
        tr.mark_proposed(d, i + 1)
        tr.mark_first_vote(d)
        tr.mark_qc_formed(d)
        tr.mark_committed(d, i + 1)
    assert len(tr.ring) == 4  # bounded ring, newest kept
    assert tr.ring[-1]["round"] == 10
    j = tr.to_json()
    assert j["commits"] == 10
    assert j["open_traces"] == 0
    for edge in ("propose_to_vote", "vote_to_qc", "qc_to_commit",
                 "propose_to_commit"):
        assert j["edges"][edge]["count"] == 10
    # each edge is one 10 ms clock tick; the total is three
    assert j["edges"]["propose_to_commit"]["mean_ms"] == pytest.approx(
        30.0, abs=0.1
    )
    # consecutive commits one round apart: gap histogram all 1s
    assert j["round_gap"]["count"] == 9


def test_trace_commit_without_proposal_counts_only():
    reg = Registry()
    tr = TraceRecorder(reg)
    tr.mark_committed(b"y" * 32, 3)  # sync'd ancestor, never proposed
    j = tr.to_json()
    assert j["commits"] == 1
    assert j["edges"]["propose_to_commit"]["count"] == 0


def test_trace_duplicate_marks_first_only():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    reg = Registry()
    tr = TraceRecorder(reg, clock=clock)
    d = b"z" * 32
    tr.mark_proposed(d, 1)
    tr.mark_first_vote(d)
    first_vote_t = tr._open[d][2]
    tr.mark_first_vote(d)  # re-delivery must not move the timestamp
    tr.mark_proposed(d, 1)
    assert tr._open[d][2] == first_vote_t


# ---- enablement / snapshot contract ------------------------------------


def test_disabled_by_default():
    assert not telemetry.enabled()
    assert telemetry.for_node("x") is None


def test_env_enablement(monkeypatch):
    monkeypatch.setenv("HOTSTUFF_TELEMETRY", "1")
    assert telemetry.enabled()
    monkeypatch.setenv("HOTSTUFF_TELEMETRY", "off")
    assert not telemetry.enabled()
    # a configured metrics port implies collection
    monkeypatch.delenv("HOTSTUFF_TELEMETRY")
    monkeypatch.setenv("HOTSTUFF_METRICS_PORT", "9464")
    assert telemetry.enabled()


def test_snapshot_carries_the_work_keys():
    """The 'Telemetry snapshot:' document must carry the node's
    verification work and the process's loop lag at top level — the
    scaling harness's scrape contract."""
    telemetry.enable()
    tel = telemetry.for_node("n0")
    work = VerifyWork()
    work.verify_calls = 5
    tel.attach_verify_work(work)
    hoststats.process().observe_lag(0.004)
    doc = tel.snapshot()
    for key in SNAPSHOT_WORK_KEYS:
        assert key in doc, f"snapshot missing work key {key!r}"
    assert doc["verify_calls"] == 5
    assert doc["loop_lag_max_ms"] >= 4.0
    assert doc["node"] == "n0"
    assert "trace" in doc
    json.dumps(doc)  # and it is one JSON-serializable log line


def test_host_stats_line_is_cumulative_but_for_the_lag_max():
    """'Host stats:' is key=value pairs a reader takes last less first;
    the lag's max alone is of the time since the last line."""
    stats = hoststats.HostStats()
    stats.observe_lag(0.010)
    stats.observe_lag(0.002)
    stats._on_gc("start", {"generation": 1})
    stats._on_gc("stop", {"generation": 1})
    stats._on_gc("start", {"generation": 2})
    stats._on_gc("stop", {"generation": 2})
    first = dict(item.split("=") for item in stats.line().split())
    assert set(first) == {
        "elapsed_s", "cpu_user_s", "cpu_sys_s", "lag_samples",
        "lag_mean_ms", "lag_max_ms", "gc2", "gc2_s",
        "store_appends", "store_records",
        "ancestor_hits", "ancestor_misses", "sync_requests",
        "wan_frames", "wan_delay_ms", "wan_base_ms", "conn_opens", "fds",
    }
    assert float(first["lag_max_ms"]) == 10.0
    assert float(first["lag_mean_ms"]) == 6.0
    assert first["lag_samples"] == "2" and first["gc2"] == "1"
    assert float(first["cpu_user_s"]) > 0
    stats.observe_lag(0.001)
    second = dict(item.split("=") for item in stats.line().split())
    assert float(second["lag_max_ms"]) == 1.0  # since the last line
    assert second["lag_samples"] == "3"  # cumulative
    assert stats.lag_json()["loop_lag_max_ms"] == 10.0  # since the start


@async_test
async def test_host_stats_probe_prints_and_uninstalls(monkeypatch):
    """The one probe samples the loop's lag, prints the line through
    the logger it is given and takes its collector hook out again."""
    import logging

    monkeypatch.setattr(hoststats, "LOG_INTERVAL", 0.05)
    monkeypatch.setattr(hoststats, "LAG_INTERVAL", 0.01)
    lines = []

    class Catch(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    logger = logging.getLogger("test.hoststats")
    logger.setLevel(logging.INFO)
    logger.addHandler(Catch())
    stats = hoststats.HostStats()
    before = len(gc.callbacks)
    task = asyncio.ensure_future(stats.run(logger))
    await asyncio.sleep(0.2)
    assert len(gc.callbacks) == before + 1
    task.cancel()
    await asyncio.gather(task, return_exceptions=True)
    assert len(gc.callbacks) == before
    assert stats.lag_samples >= 3
    assert any(line.startswith("Host stats: elapsed_s=") for line in lines)


def test_for_node_cached_per_name():
    telemetry.enable()
    assert telemetry.for_node("a") is telemetry.for_node("a")
    assert telemetry.for_node("a") is not telemetry.for_node("b")


# ---- /metrics endpoint --------------------------------------------------


async def _http_get(port: int, path: str, method: str = "GET") -> tuple[int, str, str]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"{method} {path} HTTP/1.0\r\n\r\n".encode())
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), timeout=5.0)
    writer.close()
    head, _, body = raw.decode().partition("\r\n\r\n")
    status = int(head.split()[1])
    ctype = ""
    for line in head.split("\r\n")[1:]:
        if line.lower().startswith("content-type:"):
            ctype = line.split(":", 1)[1].strip()
    return status, ctype, body


@async_test
async def test_metrics_endpoint():
    from hotstuff_tpu.telemetry.exporter import MetricsServer

    telemetry.enable()
    tel = telemetry.for_node("srv")
    tel.counter("requests_total", "Requests").inc(3)
    server = await MetricsServer(
        telemetry.registry(), host="127.0.0.1", port=0
    ).start()
    try:
        assert server.port > 0  # ephemeral port was bound and recorded
        status, ctype, body = await _http_get(server.port, "/metrics")
        assert status == 200
        assert ctype.startswith("application/openmetrics-text; version=1.0.0")
        assert 'hotstuff_requests_total{node="srv"} 3' in body
        assert body.rstrip().endswith("# EOF")

        status, ctype, body = await _http_get(server.port, "/snapshot")
        assert status == 200
        assert ctype == "application/json"
        assert json.loads(body)["srv"]["node"] == "srv"

        # delta stream: a full frame first, then O(changed) increments
        status, ctype, body = await _http_get(server.port, "/delta")
        assert status == 200
        assert ctype == "application/json"
        frame = json.loads(body)
        assert "full" in frame
        assert frame["full"]["srv.metrics.hotstuff_requests_total"] == 3
        seq = frame["seq"]
        _, _, body = await _http_get(server.port, f"/delta?since={seq}")
        again = json.loads(body)
        assert again["seq"] == seq  # nothing changed -> same frame id
        tel.counter("requests_total", "Requests").inc()
        _, _, body = await _http_get(server.port, f"/delta?since={seq}")
        delta = json.loads(body)
        assert delta.get("base") == seq
        assert delta["set"]["srv.metrics.hotstuff_requests_total"] == 4
        assert "srv.node" not in delta["set"]  # unchanged keys not resent

        status, _, _ = await _http_get(server.port, "/nope")
        assert status == 404
        status, _, _ = await _http_get(server.port, "/metrics", method="POST")
        assert status == 405
    finally:
        await server.stop()


@async_test
async def test_maybe_start_server_none_is_off():
    assert await telemetry.maybe_start_server(None) is None
    assert not telemetry.enabled()


# ---- 4-node in-process run ---------------------------------------------


@async_test
async def test_end_to_end_commit_breakdown(tmp_path):
    """A telemetry-enabled 4-node committee commits blocks and the
    commit-latency breakdown shows up in BOTH the snapshot document and
    the /metrics exposition (ISSUE 1 acceptance)."""
    from hotstuff_tpu.consensus import Consensus, Parameters
    from hotstuff_tpu.crypto import Digest, SignatureService
    from hotstuff_tpu.store import Store
    from hotstuff_tpu.telemetry.exporter import MetricsServer

    telemetry.enable()
    base = fresh_base_port()
    com = committee(base)
    nodes = []
    for i in range(4):
        name, secret = keys()[i]
        store = Store(str(tmp_path / f"db_{i}"))
        commit_q: asyncio.Queue = asyncio.Queue()
        tel = telemetry.for_node(f"node{i}")
        stack = await Consensus.spawn(
            name,
            com,
            Parameters(timeout_delay=1_000, sync_retry_delay=5_000),
            SignatureService(secret),
            store,
            commit_q,
            bind_host="127.0.0.1",
            telemetry=tel,
        )
        nodes.append((stack, commit_q, store, tel))

    async def feed():
        while True:
            digest = Digest.random()
            for stack, _, _, _ in nodes:
                await stack.tx_producer.put(digest)
            await asyncio.sleep(0.02)

    feeder = asyncio.ensure_future(feed())
    server = await MetricsServer(
        telemetry.registry(), host="127.0.0.1", port=0
    ).start()
    try:
        for _, commit_q, _, _ in nodes:
            for _ in range(3):
                await asyncio.wait_for(commit_q.get(), timeout=20.0)

        # snapshot side: every node committed and recorded edge latencies
        for _, _, _, tel in nodes:
            doc = tel.snapshot()
            assert doc["trace"]["commits"] >= 3
            edges = doc["trace"]["edges"]
            assert edges["propose_to_commit"]["count"] >= 1
            assert edges["propose_to_commit"]["mean_ms"] > 0
            assert "net" in doc  # sender pools registered
            assert "aggregator" in doc  # core section registered
            json.dumps(doc)

        # /metrics side: the same histograms render per node
        status, _, body = await _http_get(server.port, "/metrics")
        assert status == 200
        for i in range(4):
            assert (
                f'hotstuff_commit_edge_seconds_count'
                f'{{node="node{i}",edge="propose_to_commit"}}'
            ) in body
        assert "hotstuff_committed_blocks_total" in body
        assert "hotstuff_net_pool_connections" in body
    finally:
        feeder.cancel()
        await server.stop()
        for stack, _, store, _ in nodes:
            await stack.shutdown()
            store.close()


# ---- satellite regressions ---------------------------------------------


def test_raise_fd_limit_keeps_infinite_hard_cap(monkeypatch):
    """RLIM_INFINITY is -1 on Linux: max(hard, target) would replace an
    unlimited hard cap with `target` — an irreversible lowering for a
    non-root process."""
    import resource

    from hotstuff_tpu.node.main import _raise_fd_limit

    calls = []
    monkeypatch.setattr(
        resource, "getrlimit", lambda res: (1024, resource.RLIM_INFINITY)
    )
    monkeypatch.setattr(
        resource, "setrlimit", lambda res, lim: calls.append(lim)
    )
    _raise_fd_limit(50_000)
    assert calls == [(50_000, resource.RLIM_INFINITY)]


def test_raise_fd_limit_raises_finite_hard_cap(monkeypatch):
    import resource

    from hotstuff_tpu.node.main import _raise_fd_limit

    calls = []
    monkeypatch.setattr(resource, "getrlimit", lambda res: (1024, 4096))
    monkeypatch.setattr(
        resource, "setrlimit", lambda res, lim: calls.append(lim)
    )
    _raise_fd_limit(50_000)
    assert calls == [(50_000, 50_000)]


def test_raise_fd_limit_noop_when_enough(monkeypatch):
    import resource

    from hotstuff_tpu.node.main import _raise_fd_limit

    calls = []
    monkeypatch.setattr(resource, "getrlimit", lambda res: (60_000, 60_000))
    monkeypatch.setattr(
        resource, "setrlimit", lambda res, lim: calls.append(lim)
    )
    _raise_fd_limit(50_000)
    assert calls == []


def test_gc_gen2_stretch_knob(monkeypatch):
    from hotstuff_tpu.node.main import _freeze_boot_objects

    before = gc.get_threshold()
    monkeypatch.setenv("HOTSTUFF_GC_GEN2_PERIOD", "0")  # no sweeper task
    try:
        monkeypatch.setenv("HOTSTUFF_GC_GEN2_STRETCH", "0")
        _freeze_boot_objects()
        assert gc.get_threshold() == before  # opt-out keeps defaults

        monkeypatch.setenv("HOTSTUFF_GC_GEN2_STRETCH", "1")
        _freeze_boot_objects()
        assert gc.get_threshold() == (before[0], before[1], 500)
    finally:
        gc.set_threshold(*before)
        gc.unfreeze()


@async_test
async def test_reliable_connection_in_retry_is_idle():
    """A ReliableSender connection whose peer never accepts (connect
    refused, retry/backoff loop) must report idle with nothing queued —
    otherwise a dead peer pins its pool slot forever."""
    from hotstuff_tpu.network.reliable_sender import _Connection

    conn = _Connection(("127.0.0.1", fresh_base_port()))  # nothing listens
    try:
        await asyncio.sleep(0.3)  # let at least one connect attempt fail
        assert conn.connect_failures >= 1
        assert conn.idle  # evictable: no queue, no pending, no socket
    finally:
        conn.close()
        await asyncio.sleep(0)


@async_test
async def test_broadcast_pacing_ignores_unrelated_connections():
    """SimpleSender's bounded-pool pacing must count only THIS
    broadcast's connections: busy connections from other traffic on a
    shared sender previously consumed the (single, shared) 2 s deadline
    and stalled every chunk."""
    from hotstuff_tpu.network.simple_sender import SimpleSender

    loop = asyncio.get_running_loop()

    async def sink(reader, writer):
        try:
            while await reader.read(4096):
                pass
        except (ConnectionError, OSError):
            pass

    base = fresh_base_port()
    servers = [
        await asyncio.start_server(sink, "127.0.0.1", base + i)
        for i in range(3)
    ]
    sender = SimpleSender(max_conns=1)

    class _Busy:  # unrelated, permanently-busy pool entries
        idle = False

        def __init__(self):
            self.queue = asyncio.Queue()
            self.task = loop.create_task(asyncio.sleep(3600))

        def close(self):
            self.task.cancel()

    for i in range(3):
        sender._connections[("10.0.0.1", 1000 + i)] = _Busy()

    try:
        t0 = loop.time()
        await sender.broadcast(
            [("127.0.0.1", base + i) for i in range(3)], b"hello"
        )
        elapsed = loop.time() - t0
        # old code: 3 unrelated busy conns > max_conns=1 kept every chunk
        # waiting out the deadline (2 s shared). New code ignores them.
        assert elapsed < 1.5, f"broadcast stalled {elapsed:.2f}s on unrelated conns"
    finally:
        sender.close()
        for s in servers:
            s.close()
        await asyncio.sleep(0)


def test_pool_eviction_counter():
    from hotstuff_tpu.network.pool import BoundedPoolMixin

    class _Idle:
        idle = True

        class task:
            @staticmethod
            def done():
                return False

        def close(self):
            pass

    class Pool(BoundedPoolMixin):
        def __init__(self):
            self._connections = {}
            self._max_conns = 2
            self._sweeper = None

    p = Pool()
    p._connections = {i: _Idle() for i in range(5)}
    p._evict_idle(keep=2)
    assert len(p._connections) == 2
    assert p.pool_evictions == 3
