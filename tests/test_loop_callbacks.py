"""The node's event loop names what it runs while a profiler session is
active (``node/main.py`` ``_SpannedEventLoop``, ``telemetry/spans.py``
``trace_callbacks``): one ``cb.*`` span a handle, the layer spans
nested inside it, and nothing paid per callback with no session.  The
trace switch is forced here with a stub annotation, no jax needed."""

from __future__ import annotations

import asyncio
import logging
import socket
import threading
import time

import pytest

from hotstuff_tpu.analysis.framework import repo_root, run_rules
from hotstuff_tpu.analysis.rules.span_await import NoAwaitInSpan
from hotstuff_tpu.analysis.rules.taxonomy_rule import TaxonomyRegistry
from hotstuff_tpu.node.main import _new_event_loop
from hotstuff_tpu.telemetry import hoststats, spans, taxonomy

STDLIB_RUN = asyncio.events.Handle.__dict__["_run"]


class Stub:
    """A ``TraceAnnotation`` that records ``(stage, ids, depth)`` on
    enter: ``depth`` is how many annotations were open around it, and
    ``ids`` takes what ``set_metadata`` adds while it is open."""

    entered: list = []
    depth = 0

    def __init__(self, stage, /, **ids):
        self.stage, self.ids = stage, ids

    def __enter__(self):
        Stub.entered.append((self.stage, self.ids, Stub.depth))
        Stub.depth += 1
        return self

    def __exit__(self, *exc):
        Stub.depth -= 1

    def set_metadata(self, **ids):
        self.ids.update(ids)


@pytest.fixture
def traced(monkeypatch):
    Stub.entered, Stub.depth = [], 0
    monkeypatch.setattr(spans, "_tracing", lambda: True)
    monkeypatch.setattr(spans, "_ANNOTATION", Stub)
    try:
        yield Stub.entered
    finally:
        spans.trace_callbacks(False)


async def _workload() -> None:
    """A task step, a timer, a socket read through a transport and a
    threadsafe call, each with a layer span inside where the program
    would have one."""
    loop = asyncio.get_running_loop()
    a, b = socket.socketpair()
    read = loop.create_future()

    class Reader(asyncio.Protocol):
        def data_received(self, data):
            with spans.span("net.decode"):
                read.set_result(data)

    transport, _ = await loop.create_connection(Reader, sock=a)
    b.send(b"frame")
    assert await read == b"frame"
    loop.call_later(0.001, lambda: None)
    await asyncio.sleep(0.005)
    with spans.span("core.proposal", round=3):
        pass
    handed = loop.create_future()
    threading.Thread(
        target=loop.call_soon_threadsafe, args=(handed.set_result, 7)
    ).start()
    assert await handed == 7
    transport.close()
    b.close()


def _traced() -> bool:
    return asyncio.events.Handle._run is spans._traced_handle_run


def _callbacks(entered):
    """``(cb.<kind>, name)`` of each callback span, as the trace's
    reader (``chipbench/loopcalls.py``) names them."""
    return [(f"cb.{ids['kind']}", ids["name"]) for stage, ids, _ in entered
            if stage == spans.CALLBACK]


def test_each_callback_is_one_span_of_its_kind(traced):
    asyncio.run(_workload(), loop_factory=_new_event_loop)
    calls = _callbacks(traced)
    assert ("cb.io", "_SelectorSocketTransport._read_ready") in calls
    assert ("cb.timer", "_workload.<locals>.<lambda>") in calls
    assert ("cb.timer", "_set_result_unless_cancelled") in calls  # sleep
    assert ("cb.call", "Future.set_result") in calls  # threadsafe
    # the self-pipe's reader that woke the loop for it
    assert ("cb.io", "BaseSelectorEventLoop._read_from_self") in calls
    # every step and wake-up of the workload's task, by its coroutine
    assert calls.count(("cb.task", "_workload")) >= 4
    assert {stage for stage, _ in calls} == set(taxonomy.SPAN_LOOP_CALLBACKS)
    # and the stdlib's _run is back once the loop has closed
    assert asyncio.events.Handle._run is STDLIB_RUN


def test_layer_spans_nest_inside_their_callback(traced):
    asyncio.run(_workload(), loop_factory=_new_event_loop)
    depth = {stage: d for stage, _, d in traced}
    assert depth["net.decode"] == 1 and depth["core.proposal"] == 1
    # callbacks never nest, and loop.idle lies between them
    assert {d for stage, _, d in traced if stage == spans.CALLBACK} == {0}
    assert {d for stage, _, d in traced if stage == "loop.idle"} == {0}
    order = [stage for stage, _, _ in traced]
    decode = order.index("net.decode")
    assert order[decode - 1] == spans.CALLBACK
    assert traced[decode - 1][1]["kind"] == "io"


def test_off_runs_every_callback_through_the_stdlibs_run():
    """With no session the loop runs the stdlib's own ``Handle._run``
    (the same function object) and swaps nothing in."""
    seen = []

    async def main():
        await asyncio.sleep(0)
        seen.append(asyncio.events.Handle._run)
        await _workload()
        seen.append(asyncio.events.Handle._run)

    assert spans._HANDLE_RUN is STDLIB_RUN
    asyncio.run(main(), loop_factory=_new_event_loop)
    assert seen == [STDLIB_RUN, STDLIB_RUN]
    assert not _traced()


def test_the_switch_flips_at_the_loops_next_pass(monkeypatch):
    """The loop reads the switch once a pass: a session that starts is
    traced from the next pass on, one that ends gives the stdlib's
    ``_run`` back at the next pass."""
    on = [False]
    monkeypatch.setattr(spans, "_tracing", lambda: on[0])
    monkeypatch.setattr(spans, "_ANNOTATION", Stub)
    seen = []

    async def main():
        await asyncio.sleep(0)
        on[0] = True
        await asyncio.sleep(0)
        seen.append(_traced())
        on[0] = False
        await asyncio.sleep(0)
        seen.append(_traced())

    try:
        asyncio.run(main(), loop_factory=_new_event_loop)
    finally:
        spans.trace_callbacks(False)
    assert seen == [True, False]


def test_host_stats_line_carries_the_loop_threads_cpu(monkeypatch):
    """``loop_cpu_s`` is the loop thread's own CPU clock, taken when the
    probe starts on the loop: it grows while the loop spins and not
    while another thread does."""
    monkeypatch.setattr(hoststats, "LOG_INTERVAL", 0.05)
    monkeypatch.setattr(hoststats, "LAG_INTERVAL", 0.01)
    stats = hoststats.HostStats()
    assert "loop_cpu_s" not in stats.line()  # no probe, no loop thread
    readings = []

    async def main():
        probe = asyncio.ensure_future(stats.run(logging.getLogger("t")))
        await asyncio.sleep(0.02)
        for _ in range(2):
            readings.append(_loop_cpu(stats))
            spin = time.thread_time() + 0.1
            while time.thread_time() < spin:
                pass
        readings.append(_loop_cpu(stats))
        worker = threading.Thread(target=_spin_elsewhere)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
        readings.append(_loop_cpu(stats))
        probe.cancel()
        await asyncio.gather(probe, return_exceptions=True)

    asyncio.run(main(), loop_factory=_new_event_loop)
    first, second, third, after_other = readings
    assert second - first >= 0.09 and third - second >= 0.09
    assert after_other - third < 0.05


def _loop_cpu(stats) -> float:
    line = dict(item.split("=") for item in stats.line().split())
    return float(line["loop_cpu_s"])


def _spin_elsewhere() -> None:
    spin = time.thread_time() + 0.2
    while time.thread_time() < spin:
        pass


def test_the_lints_pass_with_the_callback_names():
    assert set(taxonomy.SPAN_LOOP_CALLBACKS) <= taxonomy.SPAN_STAGES
    findings = run_rules([TaxonomyRegistry(), NoAwaitInSpan()], repo_root())
    assert [f.render() for f in findings] == []


def test_the_probe_bench_splits_what_the_spans_cost():
    """``benchmark/loop_probe.py`` on a few no-op callbacks and task
    steps under a real profiler session: each kind's spans are found,
    the cost splits into its parts, and the stdlib's ``_run`` is back."""
    from benchmark import loop_probe

    out = loop_probe.measure(n=2 * loop_probe.BURST, reps=1)
    call, task = out["call"], out["task"]
    assert call["span"] > 0 and call["run"] > 0 and task["span"] > 0
    assert call["inside"] + call["outside"] == pytest.approx(call["added"])
    assert asyncio.events.Handle._run is STDLIB_RUN
