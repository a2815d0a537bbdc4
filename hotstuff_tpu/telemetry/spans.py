"""Verify-pipeline span profiler: where a claim wave's wall time goes.

The QC-256 verify read ~0.46 ms on-device but ~91 ms p50 end-to-end
(pre-chip, through the remote link) — a ~180x host-side gap that neither
the metric counters (ISSUE 1), the flight recorder (ISSUE 2), nor the
chaos plane (ISSUE 3) can attribute to a *stage*.  This module is the
missing instrument: a ring-buffered span recorder the verify pipeline
threads through every hop from claim arrival to device readback.

Span taxonomy (leaf stages sum to the wave's end-to-end time)::

    coalesce.wait    first submit -> the dispatcher collects the batch
    route.decide     the device-vs-CPU routing decision
    stage.pack       wave padding to the fixed bucket shape (ISSUE 6)
    stage.slot_wait  dispatch-loop handoff -> slot thread entry
    flatten          claims -> flat (digest, pk, sig) arrays
    prepare          host staging: decompress lookup, hashing, padding
    dispatch         kernel call (device enqueue; returns a future)
    device.execute   block_until_ready on the enqueued computation
    mesh.psum        mesh backend only: fetching the replicated QC-valid
                     word — the single psum crossing ICI (ISSUE 7); when
                     it reads 0 the sharded lane gather is skipped
    readback         device -> host transfer of the verdict lanes
    host.verify      CPU evaluation (inline route / fallback / hybrid)
    host.pairing     BLS pairing equality on the host
    verdict.fanout   worker completion -> every waiter's future resolved

plus parent spans (``e2e``, ``dispatch.wall``, ``dispatch.chunk``,
``agg.verify``, ``scheme.route``) that frame the leaves but are excluded
from waterfall sums — ``benchmark/profile.py`` renders the per-stage
waterfall and its coverage of the measured end-to-end latency.

One call, two sinks (``docs/TELEMETRY.md``, "Verify-pipeline
profiler").  ``span(name, **ids)`` is all a call site writes.  The ring
below is one sink; the other is the profiler's own trace: while a
``jax.profiler`` session is active (``start_trace``, the profiler
server, ``chipbench/child.py``'s 7 s window) every span is also entered
as a ``jax.profiler.TraceAnnotation(name, **ids)``, so it lands in the
``/host:CPU`` plane of the same ``.xplane.pb`` as the device's
``XLA Ops`` line, on the profiler's clock.  ``ids`` name the span's
cause: ``wave=<serial>`` (with ``sigs``, ``bucket`` where known) on the
verify pipeline, ``node=<8 chars>`` and ``round=<r>`` on consensus,
network, store and ingest.  A frame (``PARENT_STAGES``: the slot
thread's ``dispatch.wall``, and inside it one ``dispatch.chunk`` for
each backend call of the wave) hands its ids down to the spans entered
inside it on the same thread, so ``flatten`` ... ``readback`` carry
their wave's serial and their ``chunk`` without the backends knowing
either.  The profiler's events on one thread must nest and 64 cores
share the event-loop thread, so **a span on the loop thread never
contains an ``await``**
(lint rule ``no-await-in-span``); waits across awaits or threads are
derived by the trace's reader from the spans on either side, joined by
``wave``.

Design constraints (same contract as the journal):

- **Off by default.**  ``HOTSTUFF_PROFILE=1`` / ``--profile`` /
  :func:`enable` turn the ring on; a profiler session turns the trace
  sink on.  With neither, :func:`span` returns one shared no-op context
  manager and :func:`recorder` returns ``None`` — no clock reads, one
  module-global test and one ``is_enabled()`` per call site (asserted
  < 2% of a 1k-claim wave in tests/test_profile.py).
- **Bounded.**  Completed spans land in a ``deque(maxlen=capacity)``
  ring (default 65536): a run that outlives the ring loses its OLDEST
  spans, a flight recorder, not an archive.
- **Thread-correct.**  The dispatcher's event loop and the verify
  worker thread both record; ``perf_counter_ns`` is CLOCK_MONOTONIC
  (cross-thread consistent) and per-thread nesting depth lives in a
  ``threading.local``.

Fan-out when a span completes (both optional, both pull their switches
once): a ``verify_stage_ms{stage=...}`` histogram in the telemetry
registry, and — when a journal is attached — an ``{"e":"span"}`` record
whose ``u`` field carries the duration, rendered by
``benchmark/traces.py`` as a per-node "verify pipeline" Perfetto track
aligned with the consensus rounds.

The event loop's own callbacks (``trace_callbacks``): while a session is
active the node's loop (``node/main.py``) has every handle it runs
entered as one ``cb`` annotation carrying ``kind`` (``task`` / ``io`` /
``timer`` / ``call``, read as ``cb.task`` ... ``cb.call``) and
``name=<qualname>``, both set once the annotation has started, so the
layer spans nest inside the callback that ran them, what lies between
the spans has a name, and the classifying is the callback's time.
With no session ``asyncio.events.Handle._run`` is the stdlib's own
function: nothing is paid per callback.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
from collections import deque
from contextlib import nullcontext

from .taxonomy import (
    SPAN_ANNOTATION_STAGES as ANNOTATION_STAGES,
    SPAN_LEAF_STAGES as LEAF_STAGES,
    SPAN_PARENT_STAGES as PARENT_STAGES,
)

DEFAULT_CAPACITY = 65536

#: stage-duration histogram bounds in MILLISECONDS: 1 us doubling up to
#: ~134 s — one ladder below the consensus LATENCY_BOUNDS_S so sub-0.1 ms
#: device stages (dispatch ~50 us) don't collapse into the first bucket
STAGE_BOUNDS_MS: tuple[float, ...] = tuple(1e-3 * 2**i for i in range(28))

# the stage tables themselves (leaf pipeline order, parent frames, value
# annotations) live in telemetry/taxonomy.py — the one registry the
# analysis plane lints against and benchmark/traces.py renders from;
# the LEAF_STAGES / PARENT_STAGES / ANNOTATION_STAGES re-exports above
# keep benchmark/profile.py and existing call sites working unchanged

_RECORDER: "SpanRecorder | None" = None
_ENV_CHECKED = False
_SINK = None  # journal fan-out: fn(stage, dur_ns), set via attach_journal
_NULL = nullcontext()  # the shared disabled-path context (reentrant)
_LOCAL = threading.local()  # per-thread nesting depth and inherited ids
_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _no_session() -> bool:
    """Is a profiler session active?  Only a process that has imported
    jax can hold one, and this module never imports it first: once jax
    is there, the profiler's own ``is_enabled`` takes this function's
    place."""
    global _ANNOTATION, _tracing
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation

    _ANNOTATION = TraceAnnotation
    _tracing = TraceAnnotation.is_enabled
    return _tracing()


_tracing = _no_session


def _env_on() -> bool:
    env = os.environ.get("HOTSTUFF_PROFILE")
    return env is not None and env.strip().lower() not in (
        "", "0", "false", "no", "off",
    )


def recorder() -> "SpanRecorder | None":
    """The live recorder, or None when profiling is off.  Call sites
    guard manual timing with ``rec = spans.recorder(); if rec is not
    None: ...`` — the disabled path is one global read (plus a one-time
    env check the first call pays)."""
    global _RECORDER, _ENV_CHECKED
    if _RECORDER is not None:
        return _RECORDER
    if not _ENV_CHECKED:
        _ENV_CHECKED = True
        if _env_on():
            _RECORDER = SpanRecorder()
    return _RECORDER


def span(name: str, **ids):
    """``with spans.span("prepare", wave=7): ...`` — a timed span in the
    ring when profiling is on, an annotation in the profiler's trace
    while a session is active, the shared no-op context otherwise."""
    rec = _RECORDER if _ENV_CHECKED else recorder()
    if _tracing():
        if rec is None and name not in PARENT_STAGES:
            # the trace alone, and no ids to hand down: the profiler's
            # own context manager, with nothing of ours around it
            frame = getattr(_LOCAL, "ids", None)
            if frame:
                ids = {**frame, **ids}
            return _ANNOTATION(name, **ids)
        return _Span(rec, name, ids, True)
    return _NULL if rec is None else _Span(rec, name, ids, False)


def enabled() -> bool:
    return recorder() is not None


def enable(capacity: int = DEFAULT_CAPACITY) -> "SpanRecorder":
    """Force-enable profiling (the CLI's --profile and the profile
    bench call this); idempotent — an existing recorder is kept."""
    global _RECORDER
    if _RECORDER is None:
        _RECORDER = SpanRecorder(capacity)
    return _RECORDER


def disable() -> None:
    """Drop the recorder and re-arm the env check (tests)."""
    global _RECORDER, _ENV_CHECKED, _SINK
    _RECORDER = None
    _ENV_CHECKED = False
    _SINK = None


def attach_journal(journal) -> None:
    """Fan completed spans out into ``journal`` as ``{"e":"span"}``
    records (stage in ``p``, duration ns in ``u``).  First journal wins:
    spans are process-wide (the verify service is shared across a
    co-located committee), so the whole pipeline renders as ONE track
    pinned to the first journaled node."""
    global _SINK
    if _SINK is None and journal is not None:
        _SINK = lambda stage, dur_ns: journal.record(
            "span", 0, None, stage, dur_ns=dur_ns
        )


# ---- the event loop's callbacks ------------------------------------------

#: the stdlib's own ``Handle._run`` (``TimerHandle`` inherits it): what
#: every callback runs through while no profiler session is active
_HANDLE_RUN = asyncio.events.Handle._run


#: the one annotation a callback is entered as; its kind (``task``,
#: ``io``, ``timer``, ``call``) and name are set inside it, so that the
#: probe's own work lies inside the span it measures, not between spans
CALLBACK = "cb"
_TRANSPORT = asyncio.selector_events._SelectorTransport


def _qualname(obj) -> str:
    return getattr(obj, "__qualname__", None) or type(obj).__qualname__


def _callback_kind(handle) -> tuple[str, str]:
    """``(kind, name)`` of a handle's run: a Task's step or wake-up (its
    coroutine's qualname), a timer, a selector transport's or the loop's
    own reader or writer (``_read_ready``, ``_write_ready``, the
    self-pipe's ``_read_from_self``, ``_accept_connection``), or any
    other ready handle."""
    callback = handle._callback
    owner = getattr(callback, "__self__", None)
    if isinstance(owner, asyncio.Task):
        return "task", _qualname(owner.get_coro())
    if isinstance(handle, asyncio.TimerHandle):
        return "timer", _qualname(callback)
    if owner is handle._loop or isinstance(owner, _TRANSPORT):
        return "io", _qualname(callback)
    return "call", _qualname(callback)


def _traced_handle_run(handle) -> None:
    with _ANNOTATION(CALLBACK) as note:
        kind, name = _callback_kind(handle)
        note.set_metadata(kind=kind, name=name)
        _HANDLE_RUN(handle)


def trace_callbacks(on: bool) -> None:
    """Enter every handle any loop of the process runs as a ``cb.*``
    span (``on``), or give ``Handle._run`` back to the stdlib.  The
    node's loop calls this when the profiler's switch flips."""
    asyncio.events.Handle._run = _traced_handle_run if on else _HANDLE_RUN


class _Span:
    """One live span (context manager) feeding either sink or both.
    Cheap by construction: two clock reads, a thread-local depth bump
    and one ring append on exit; one ``TraceAnnotation`` when traced,
    entered last and left first so that the trace's interval holds the
    call site's work and little of this class's."""

    __slots__ = ("_rec", "name", "ids", "_outer", "_note", "t0", "depth")

    def __init__(self, rec: "SpanRecorder | None", name: str, ids: dict,
                 traced: bool):
        self._rec = rec
        self.name = name
        self.ids = ids
        self._outer = None
        self._note = traced
        self.t0 = 0
        self.depth = 0

    def __enter__(self) -> "_Span":
        local = _LOCAL
        self.depth = getattr(local, "depth", 0)
        local.depth = self.depth + 1
        frame = self._outer = getattr(local, "ids", None)
        if frame:
            # the cause of a span inside a frame is the frame's
            self.ids = {**frame, **self.ids}
        if self.name in PARENT_STAGES:
            local.ids = self.ids
        self.t0 = time.perf_counter_ns()
        if self._note:
            self._note = _ANNOTATION(self.name, **self.ids)
            self._note.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._note:
            self._note.__exit__(*exc)
        dur = time.perf_counter_ns() - self.t0
        _LOCAL.depth = self.depth
        _LOCAL.ids = self._outer
        if self._rec is not None:
            self._rec._emit(self.name, self.t0, dur, self.depth, self.ids)


class SpanRecorder:
    """Ring buffer of completed spans ``(name, t0_ns, dur_ns, depth,
    thread, ids)`` with optional metric/journal fan-out."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(1, int(capacity))
        self._ring: deque = deque(maxlen=self.capacity)
        self.spans_total = 0
        # None = undecided (checked on the first span so tests that
        # enable telemetry before profiling are seen); False = off
        self._metrics_on: bool | None = None
        self._hists: dict[str, object] = {}

    # ---- recording -------------------------------------------------------

    def span(self, name: str, **ids) -> _Span:
        return _Span(self, name, ids, _tracing())

    def add(self, name: str, t0_ns: int, dur_ns: int, **ids) -> None:
        """A manually-timed span, in the ring only: a wait across an
        ``await`` or between threads (``coalesce.wait`` from submit
        stamps, ``stage.slot_wait``), which the profiler's trace must
        not hold because it does not nest."""
        self._emit(name, t0_ns, max(0, int(dur_ns)), 0, ids)

    def _emit(
        self, name: str, t0_ns: int, dur_ns: int, depth: int, ids: dict
    ) -> None:
        self._ring.append(
            (name, t0_ns, dur_ns, depth, threading.current_thread().name, ids)
        )
        self.spans_total += 1
        if self._metrics_on is None:
            from hotstuff_tpu import telemetry

            self._metrics_on = telemetry.enabled()
        if self._metrics_on:
            hist = self._hists.get(name)
            if hist is None:
                from hotstuff_tpu import telemetry

                hist = self._hists[name] = telemetry.registry().histogram(
                    "verify_stage_ms",
                    "Verify-pipeline stage durations (milliseconds)",
                    {"stage": name},
                    bounds=STAGE_BOUNDS_MS,
                )
            # annotation stages carry a value in the dur field, not
            # nanoseconds — observe it raw (e.g. in-flight wave depth)
            hist.observe(
                dur_ns if name in ANNOTATION_STAGES else dur_ns / 1e6
            )
        sink = _SINK
        if sink is not None:
            try:
                sink(name, dur_ns)
            except Exception:  # noqa: BLE001 — profiling must never kill
                pass  # the pipeline it observes

    # ---- draining --------------------------------------------------------

    def snapshot(self) -> list[tuple]:
        return list(self._ring)

    def drain(self) -> list[tuple]:
        out = list(self._ring)
        self._ring.clear()
        return out

    def stats(self) -> dict:
        return {
            "spans": self.spans_total,
            "buffered": len(self._ring),
            "capacity": self.capacity,
            "dropped": max(0, self.spans_total - self.capacity),
        }


__all__ = [
    "SpanRecorder",
    "DEFAULT_CAPACITY",
    "STAGE_BOUNDS_MS",
    "LEAF_STAGES",
    "PARENT_STAGES",
    "ANNOTATION_STAGES",
    "recorder",
    "span",
    "enabled",
    "enable",
    "disable",
    "attach_journal",
    "trace_callbacks",
]
