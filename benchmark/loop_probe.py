"""`python -m benchmark.loop_probe` — what the loop's callback spans
cost on the host it runs on.

While a profiler session is active the node's loop enters every handle
it runs as one ``cb`` span (``telemetry/spans.py`` ``trace_callbacks``).
That span is the probe, and part of its cost lies outside it: the call
into the traced ``_run``, the annotation's start and its end.  In a
traced chip run that part reads as loop machinery, so
``host.loop_machinery_ms_per_round`` holds it.  This bench measures it
on bursts of no-op callbacks, untraced and traced, on the node's own
loop, and prints one JSON line of nanoseconds a callback:

- ``untraced`` / ``traced``: the burst's wall time over its callbacks;
- ``added``: traced less untraced, the probe's whole cost;
- ``run``: the stdlib ``Handle._run`` of the same callback, alone;
- ``inside``: the probe's part inside the span (a span's mean length
  less ``run``), which a traced run counts as callback self time;
- ``outside``: ``added`` less ``inside``, which it counts as machinery.

The same for a task that steps by ``await asyncio.sleep(0)`` (``task``:
``added`` and the span's mean length only; a step has no stdlib run to
time alone).  Medians over ``--reps`` alternations.  The trace is read
in this process, so run it with ``JAX_PLATFORMS=cpu`` where a chip is
held by another process.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import statistics
import sys
import tempfile
import time

BURST = 1000


class _Count:
    """A no-op callback that counts its calls down and resolves
    ``done`` at zero."""

    def __init__(self, n: int, done):
        self.left, self.done = n, done

    def __call__(self) -> None:
        self.left -= 1
        if not self.left:
            self.done.set_result(None)


async def _calls(n: int) -> None:
    loop = asyncio.get_running_loop()
    for _ in range(n // BURST):
        done = loop.create_future()
        count = _Count(BURST, done)
        for _ in range(BURST):
            loop.call_soon(count)
        await done


async def _steps(n: int) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def _run_ns(reps: int = 200_000) -> float:
    """The stdlib's ``Handle._run`` of one such callback, alone."""
    loop = asyncio.new_event_loop()
    try:
        handle = asyncio.Handle(_Count(reps + 1, None), (), loop)
        run = asyncio.events.Handle._run
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            run(handle)
        return (time.perf_counter_ns() - t0) / reps
    finally:
        loop.close()


async def _timed(work, n: int, trace_dir: str | None) -> float:
    import jax

    await asyncio.sleep(0)
    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        await asyncio.sleep(0)  # the loop flips Handle._run at this pass
    t0 = time.perf_counter_ns()
    await work(n)
    wall = time.perf_counter_ns() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return wall / n


def _span_ns(trace_dir: str, name: str) -> float | None:
    """Mean length of the ``cb`` spans of ``name`` on the thread that
    holds most of them."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")
    ))[-1]
    best: list[int] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lengths = [
                    int(e.duration_ns) for e in line.events
                    if e.name == "cb" and dict(e.stats).get("name") == name
                ]
                if len(lengths) > len(best):
                    best = lengths
    return sum(best) / len(best) if best else None


def measure(n: int = 20_000, reps: int = 3) -> dict:
    from hotstuff_tpu.node.main import _new_event_loop

    out: dict[str, dict[str, list[float]]] = {"call": {}, "task": {}}
    for work, kind, name in ((_calls, "call", "_Count"),
                             (_steps, "task", "_timed")):
        seen = out[kind]
        for _ in range(reps):
            with tempfile.TemporaryDirectory() as trace_dir:
                untraced = asyncio.run(
                    _timed(work, n, None), loop_factory=_new_event_loop
                )
                traced = asyncio.run(
                    _timed(work, n, trace_dir), loop_factory=_new_event_loop
                )
                span = _span_ns(trace_dir, name)
            for key, value in (("untraced", untraced), ("traced", traced),
                               ("added", traced - untraced), ("span", span)):
                seen.setdefault(key, []).append(value)
    result = {
        kind: {k: statistics.median(v) for k, v in seen.items()
               if None not in v}
        for kind, seen in out.items()
    }
    call = result["call"]
    call["run"] = _run_ns()
    if "span" in call:
        call["inside"] = call["span"] - call["run"]
        call["outside"] = call["added"] - call["inside"]
    result["callbacks"] = n
    return result


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.loop_probe")
    parser.add_argument("--callbacks", type=int, default=20_000)
    parser.add_argument("--reps", type=int, default=3)
    args = parser.parse_args(argv)
    json.dump(measure(args.callbacks, args.reps), sys.stdout)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
