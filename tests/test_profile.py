"""Verify-pipeline profiler tests (ISSUE 4).

Covers the tentpole pieces — the span recorder (off-by-default
zero-allocation contract, nesting, ring bound, metric/journal fan-out),
the ``python -m benchmark profile`` waterfall math and SUMMARY
rendering, the journal ``"u"`` duration wire field and its Perfetto
"verify pipeline" track — plus the tier-1 overhead bound: profiling
disabled must cost <2% of a real QC claim wave.
"""

from __future__ import annotations

import asyncio
import os
import time

import pytest

from hotstuff_tpu import telemetry
from hotstuff_tpu.telemetry import spans
from hotstuff_tpu.telemetry.journal import Journal

from .common import async_test, committee, fresh_base_port, keys


@pytest.fixture(autouse=True)
def _clean_profiler(monkeypatch):
    """Profiler/telemetry state is process-global: every test starts
    disabled with the env check re-armed, and leaves it that way."""
    monkeypatch.delenv("HOTSTUFF_TELEMETRY", raising=False)
    monkeypatch.delenv("HOTSTUFF_PROFILE", raising=False)
    monkeypatch.delenv("HOTSTUFF_FORCE_DEVICE_ROUTE", raising=False)
    telemetry.reset()
    spans.disable()
    yield
    telemetry.reset()
    spans.disable()


# ---- span recorder ------------------------------------------------------


def test_disabled_is_shared_noop():
    """Off by default: no recorder, and span() hands every call site the
    SAME no-op context manager — zero allocation on the hot path."""
    assert spans.recorder() is None
    assert not spans.enabled()
    assert spans.span("prepare") is spans.span("dispatch")
    with spans.span("prepare"):
        pass  # and it is a usable (reentrant) context manager


def test_disabled_path_allocates_nothing():
    """With no ring and no profiler session a call site keeps nothing
    alive: every call returns the one shared context, whatever ids it
    passes, and the interpreter's block count does not grow."""
    import sys

    assert spans.recorder() is None
    for _ in range(100):  # warm the call site
        spans.span("core.vote", node="abcdefgh", round=3)
    before = sys.getallocatedblocks()
    for i in range(10_000):
        assert spans.span("core.vote", node="abcdefgh", round=3) is spans._NULL
    assert sys.getallocatedblocks() - before < 50


def test_ids_reach_the_ring_and_nest():
    """A span carries its ids into the ring; a span entered inside a
    frame inherits the frame's ids (a wave's stage spans get ``wave``
    from the ``dispatch.wall`` frame around them; a leaf hands nothing
    down), and manual ``add`` waits carry theirs."""
    rec = spans.enable()
    with spans.span("dispatch.wall", wave=7, sigs=38):
        with spans.span("prepare", bucket=128):
            pass
    with spans.span("core.vote", node="abcdefgh", round=3):
        with spans.span("store.write", node="abcdefgh"):
            pass
    rec.add("coalesce.wait", 0, 5, wave=7)
    rows = {r[0]: r for r in rec.drain()}
    assert rows["store.write"][5] == {"node": "abcdefgh"}  # not a frame's
    assert rows["dispatch.wall"][5] == {"wave": 7, "sigs": 38}
    assert rows["prepare"][5] == {"wave": 7, "sigs": 38, "bucket": 128}
    assert rows["prepare"][3] == 1  # depth
    assert rows["core.vote"][5] == {"node": "abcdefgh", "round": 3}
    assert rows["coalesce.wait"][5] == {"wave": 7}
    # and nothing leaks out of the frame
    with spans.span("flatten"):
        pass
    assert rec.drain()[0][5] == {}


def _host_plane_events(trace_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return [
        (event.name, dict(event.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for event in line.events
    ]


def test_span_lands_in_the_profilers_trace(tmp_path):
    """The second sink: under ``jax.profiler.start_trace`` (on the CPU
    here) a span is a ``TraceAnnotation`` in the host plane of the same
    ``.xplane.pb`` a device's operations go to, with its ids; the ring
    gets the same span when it is on, and nothing is annotated once the
    session has stopped."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as chipbench/child.py traces
    assert spans.span("prepare", wave=7) is spans._NULL  # no session yet
    rec = spans.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans.span("dispatch.wall", wave=7):
            with spans.span("prepare", bucket=128):
                time.sleep(0.001)
        with spans.span("core.vote", node="abcdefgh", round=3):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [r[0] for r in rec.drain()] == [
        "prepare", "dispatch.wall", "core.vote"
    ]
    spans.disable()  # the trace sink alone now
    assert spans.span("prepare", wave=7) is spans._NULL  # session over
    events = _host_plane_events(str(tmp_path))
    prepare = [stats for name, stats in events if name == "prepare"]
    assert prepare == [{"wave": 7, "bucket": 128}]
    assert ("core.vote", {"node": "abcdefgh", "round": 3}) in events


def test_env_knob(monkeypatch):
    monkeypatch.setenv("HOTSTUFF_PROFILE", "1")
    spans.disable()  # re-arm the one-time env check
    assert spans.recorder() is not None
    monkeypatch.setenv("HOTSTUFF_PROFILE", "off")
    spans.disable()
    assert spans.recorder() is None


def test_nesting_depth_and_order():
    rec = spans.enable()
    with spans.span("e2e"):
        with spans.span("prepare"):
            pass
        with spans.span("dispatch"):
            pass
    rows = rec.drain()
    # children append on exit, so they precede their parent in the ring
    names = [r[0] for r in rows]
    assert names == ["prepare", "dispatch", "e2e"]
    depths = {r[0]: r[3] for r in rows}
    assert depths == {"e2e": 0, "prepare": 1, "dispatch": 1}
    assert all(r[2] >= 0 for r in rows)  # durations are non-negative ns


def test_ring_bound_and_stats():
    rec = spans.SpanRecorder(capacity=4)
    for i in range(10):
        rec.add("flatten", 0, i)
    assert len(rec.snapshot()) == 4
    # the ring keeps the NEWEST spans (flight recorder, not archive)
    assert [r[2] for r in rec.snapshot()] == [6, 7, 8, 9]
    st = rec.stats()
    assert st["spans"] == 10 and st["dropped"] == 6 and st["capacity"] == 4
    rec.drain()
    assert rec.stats()["buffered"] == 0


def test_metrics_fanout():
    """With telemetry on, completed spans feed the per-stage
    verify_stage_ms histogram."""
    telemetry.enable()
    spans.enable()
    with spans.span("device.execute"):
        time.sleep(0.001)
    text = telemetry.registry().render_prometheus()
    assert "verify_stage_ms" in text
    assert 'stage="device.execute"' in text


def test_journal_u_roundtrip_and_trace_track(tmp_path):
    """Span records land in the journal with the ``"u"`` duration field
    and render as the per-node tid=1 'verify pipeline' Perfetto track."""
    from benchmark.traces import TraceSet, load_journals

    journal = Journal("nodeA", str(tmp_path), buffer_records=1)
    spans.enable()
    spans.attach_journal(journal)
    with spans.span("dispatch"):
        time.sleep(0.0005)
    journal.close()

    journals = load_journals(str(tmp_path))
    recs = [r for r in journals["nodeA"] if r["e"] == "span"]
    assert len(recs) == 1
    assert recs[0]["p"] == "dispatch"
    assert recs[0]["u"] >= 500_000  # the slept 0.5 ms, in ns

    ts = TraceSet.load(str(tmp_path))
    assert ts.verify_spans["nodeA"]
    assert "Verify-pipeline spans journaled: 1" in ts.summary()
    doc = ts.chrome_trace()
    slices = [e for e in doc["traceEvents"] if e.get("cat") == "verify"]
    assert len(slices) == 1
    assert slices[0]["name"] == "dispatch" and slices[0]["tid"] == 1
    tracks = [
        e
        for e in doc["traceEvents"]
        if e.get("name") == "thread_name"
        and e["args"]["name"] == "verify pipeline"
    ]
    assert len(tracks) == 1


def test_attach_journal_first_wins(tmp_path):
    j1 = Journal("n1", str(tmp_path / "a"), buffer_records=1)
    j2 = Journal("n2", str(tmp_path / "b"), buffer_records=1)
    spans.enable()
    spans.attach_journal(j1)
    spans.attach_journal(j2)  # ignored: spans are process-wide
    with spans.span("flatten"):
        pass
    j1.close()
    j2.close()
    assert j1.records_total == 1
    assert j2.records_total == 0


def test_journal_sink_failure_is_swallowed():
    class Exploding:
        def record(self, *a, **kw):
            raise RuntimeError("disk full")

    rec = spans.enable()
    spans.attach_journal(Exploding())
    with spans.span("prepare"):
        pass  # must not raise
    assert rec.stats()["spans"] == 1


# ---- waterfall math / SUMMARY rendering ---------------------------------


def _rows(name, durs_ms):
    return [(name, 0, int(d * 1e6), 0, "t") for d in durs_ms]


def test_waterfall_coverage_and_multifire():
    from benchmark.profile import waterfall

    e2e = [10.0, 10.0, 10.0, 10.0]
    rows = (
        _rows("prepare", [4.0] * 4)
        + _rows("device.execute", [5.0] * 4)
        # multi-fire: 2 dispatch spans per wave must charge 2 x p50
        + _rows("dispatch", [0.5] * 8)
        # parent frame: reported, never summed into coverage
        + _rows("e2e", [10.0] * 4)
    )
    res = waterfall(rows, e2e)
    assert res["e2e_ms"]["p50"] == 10.0
    assert res["waves"] == 4
    assert res["stages"]["prepare"]["pct_of_e2e"] == 40.0
    assert res["stages"]["dispatch"]["pct_of_e2e"] == 10.0
    assert res["stages"]["dispatch"]["count"] == 8
    assert res["stages"]["e2e"]["p50_ms"] == 10.0
    assert res["coverage_pct"] == pytest.approx(100.0, abs=0.1)


def test_waterfall_empty_is_safe():
    from benchmark.profile import waterfall

    res = waterfall([], [])
    assert res["coverage_pct"] == 0.0
    assert res["e2e_ms"]["p50"] == 0.0


def test_format_waterfall_summary():
    from benchmark.profile import format_waterfall, waterfall

    res = {
        "verifier": "tpu",
        "route": "device",
        "waves": 4,
        "sizes": {
            256: waterfall(
                _rows("prepare", [4.0] * 4) + _rows("e2e", [10.0] * 4),
                [10.0] * 4,
            )
        },
    }
    text = format_waterfall(res)
    assert "PROFILE SUMMARY" in text
    assert "QC size 256" in text
    assert "prepare" in text and "(frame)" in text
    assert "coverage:" in text


# ---- wave-train mode (ISSUE 5) ------------------------------------------


def test_make_train_claims_distinct_digests_one_committee():
    """Every wave carries a DISTINCT digest (defeats the service's
    cross-wave claim dedup) signed by the SAME committee (keeps the
    device-resident key cache hot across the train)."""
    from benchmark.profile import make_train_claims

    claims, pks = make_train_claims(4, waves=3)
    assert len(claims) == 3 and len(pks) == 4
    digests = [c[1] for c in claims]
    assert len(set(digests)) == 3
    for kind, _digest, votes in claims:
        assert kind == "shared" and len(votes) == 4
        assert [pk for pk, _sig in votes] == pks
    # and the claims are genuinely valid QC-shaped work
    from hotstuff_tpu.crypto.async_service import eval_claims_sync
    from hotstuff_tpu.crypto.service import CpuVerifier

    assert eval_claims_sync(CpuVerifier(), claims) == [True] * 3


def test_format_train_summary():
    from benchmark.profile import format_train

    result = {
        "verifier": "tpu",
        "qc_size": 256,
        "train_waves": 8,
        "reps": 3,
        "depths": {
            1: {
                "single_wave_p50_ms": 2.0,
                "train_p50_ms": 16.0,
                "amortized_wave_ms": 2.0,
                "peak_inflight": 1,
                "train_sigs_per_s": 128_000.0,
            },
            2: {
                "single_wave_p50_ms": 2.0,
                "train_p50_ms": 12.0,
                "amortized_wave_ms": 1.5,
                "peak_inflight": 2,
                "train_sigs_per_s": 170_000.0,
            },
        },
        "overlap_speedup": 1.33,
        "overlap_efficiency_pct": 25.0,
    }
    text = format_train(result)
    assert "sustained verify wave-train" in text
    assert "QC size 256" in text and "8 waves/train" in text
    assert "1.33x depth-1" in text
    assert "25.0% of the per-wave round trip hidden" in text


# ---- overhead bound (tier-1 acceptance) ---------------------------------


def test_disabled_overhead_under_2pct():
    """Profiling disabled must cost <2% of a 1k-claim wave: the pipeline
    makes at most ~32 span()/recorder() probes per wave, so 32x the
    per-probe disabled cost must sit under 2% of a real wave's time."""
    from benchmark.profile import make_qc_claim
    from hotstuff_tpu.crypto.async_service import eval_claims_sync
    from hotstuff_tpu.crypto.service import CpuVerifier

    assert spans.recorder() is None  # profiling off

    claim, _pks = make_qc_claim(256)
    backend = CpuVerifier()
    assert eval_claims_sync(backend, [claim]) == [True]  # warm
    t0 = time.perf_counter()
    assert eval_claims_sync(backend, [claim]) == [True]
    wave_s = time.perf_counter() - t0

    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        spans.span("prepare", wave=7, bucket=128)
        spans.recorder()
    per_probe_s = (time.perf_counter() - t0) / n

    budget = 0.02 * wave_s
    assert 32 * per_probe_s < budget, (
        f"32 disabled probes cost {32 * per_probe_s * 1e6:.1f} us, "
        f"budget {budget * 1e6:.1f} us (wave {wave_s * 1e3:.2f} ms)"
    )


# ---- enabled end-to-end: committee still commits (slow tier) ------------


@pytest.mark.slow
@async_test
async def test_profiled_committee_still_commits(tmp_path):
    """With the profiler AND journaling on, a 4-node committee keeps
    committing, and the merged trace carries BOTH consensus round slices
    and the verify-pipeline track on one timeline (ISSUE 4 acceptance)."""
    from benchmark.profile import make_qc_claim
    from benchmark.traces import TraceSet
    from hotstuff_tpu.consensus import Consensus, Parameters
    from hotstuff_tpu.crypto import Digest, SignatureService
    from hotstuff_tpu.crypto.async_service import AsyncVerifyService
    from hotstuff_tpu.crypto.service import CpuVerifier
    from hotstuff_tpu.store import Store

    telemetry.enable()
    spans.enable()
    jdir = str(tmp_path / "journals")
    base = fresh_base_port()
    com = committee(base)
    nodes = []
    for i in range(4):
        name, secret = keys()[i]
        store = Store(str(tmp_path / f"db_{i}"))
        commit_q: asyncio.Queue = asyncio.Queue()
        tel = telemetry.for_node(str(name)[:8])
        journal = Journal(str(name)[:8], jdir, buffer_records=8)
        tel.attach_journal(journal)
        if i == 0:  # the process-wide span track pins to the first node
            spans.attach_journal(journal)
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
        nodes.append((stack, commit_q, store, journal))

    async def feed():
        while True:
            digest = Digest.random()
            for stack, _, _, _ in nodes:
                await stack.tx_producer.put(digest)
            await asyncio.sleep(0.02)

    feeder = asyncio.ensure_future(feed())
    try:
        # drive one claim wave through the production dispatch path
        # while the committee runs, so verify spans land in the journal
        svc = AsyncVerifyService(CpuVerifier())
        assert (await svc.verify_claims([make_qc_claim(8)[0]])) == [True]
        for _, commit_q, _, _ in nodes:
            for _ in range(2):
                await asyncio.wait_for(commit_q.get(), timeout=20.0)
    finally:
        feeder.cancel()
        for stack, _, store, journal in nodes:
            await stack.shutdown()
            journal.close()
            store.close()

    ts = TraceSet.load(jdir)
    assert len(ts.committed()) >= 2
    assert ts.verify_spans  # span records survived the merge
    assert "Verify-pipeline spans journaled" in ts.summary()
    doc = ts.chrome_trace()
    cats = {e.get("cat") for e in doc["traceEvents"]}
    assert "block" in cats and "verify" in cats
    verify_stages = {
        e["name"] for e in doc["traceEvents"] if e.get("cat") == "verify"
    }
    # the CPU-inline wave's pipeline stages are on the track, on tid 1
    assert {"flatten", "host.verify"} <= verify_stages
    assert all(
        e["tid"] == 1
        for e in doc["traceEvents"]
        if e.get("cat") == "verify"
    )
