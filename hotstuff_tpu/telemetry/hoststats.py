"""What the process itself costs the host: CPU seconds, event-loop
lag and the collector's full passes, printed whether tracing is on or
off.

One ``Host stats:`` line per process every ``LOG_INTERVAL`` seconds,
cumulative like ``Verify service stats`` (a reader takes last less
first over its window)::

    Host stats: elapsed_s=35.004 cpu_user_s=30.21 cpu_sys_s=3.02
      loop_cpu_s=24.80
      lag_samples=640 lag_mean_ms=1.25 lag_max_ms=41.7 gc2=1 gc2_s=0.038
      store_appends=5210 store_records=38877
      ancestor_hits=24310 ancestor_misses=0 sync_requests=0
      wan_frames=0 wan_delay_ms=0.0 wan_base_ms=0.0
      conn_opens=8064 fds=16412

- ``cpu_user_s`` / ``cpu_sys_s``: the process's CPU seconds, all threads
  (``os.times``).  Over a window's wall time they say whether a long
  round is the CPU's or a wait's.
- ``loop_cpu_s``: the event-loop thread's own CPU seconds (its
  ``pthread_getcpuclockid`` clock, taken when the probe starts on the
  loop).  The loop's busy wall time less these is time it was runnable
  but off a core: waiting for the interpreter's lock, or not scheduled.
- ``lag_*``: the event-loop lag probe, the one probe the process has: a
  ``LAG_INTERVAL`` sleep wakes late by the time the loop was busy or
  the process was not scheduled.  ``lag_samples`` and ``lag_mean_ms``
  are cumulative; ``lag_max_ms`` is the largest lag since the LAST line,
  so a reader takes the largest line in its window.
- ``gc2`` / ``gc2_s``: generation-2 collections and the seconds they
  took (``gc.callbacks``): the collector's pauses, told apart from the
  machine's.
- ``store_appends`` / ``store_records``: writes of a WAL and the records
  they carried, every store engine of the process
  (``store/engine.py`` ``WAL_COUNTS``): records over appends is how far
  the write batch engages.
- ``ancestor_hits`` / ``ancestor_misses``: parent lookups of every
  node's synchronizer (``consensus/synchronizer.py``
  ``get_parent_block``) answered from the blocks it keeps, and those
  that went on to the store; the genesis answer is neither.  Hits over
  both is how often a node is spared a second decode of a block it has
  just processed.  ``sync_requests``: parent requests those
  synchronizers sent to peers, first asks and retries: a block that
  reached a node before its parent.
- ``wan_frames`` / ``wan_delay_ms`` / ``wan_base_ms``: frames the WAN
  emulation held at a sender, the sum of what it held them for and the
  sum of their links' matrix entries (``network/wan.py`` ``WAN_COUNTS``,
  counted where ``LinkScheduler.deliver_at`` draws); all 0 without
  ``HOTSTUFF_WAN_SPEC``.  Delay over frames is the mean injected
  one-way delay; delay over base is held to 1 within 2% (the spec's
  ``injected_delay`` guarantee).
- ``conn_opens``: node-to-node connections the senders opened, every
  sender of every node (``network/pool.py`` ``CONN_COUNTS``): it climbs
  through a committee's first rotation and stands still after it where
  the pools are unbounded, and climbs every round where they are bounded
  (``HOTSTUFF_MAX_PEER_CONNS``).
- ``fds``: the process's open file descriptors when the line was made,
  counted off the event loop on a worker thread (a listing of
  ``/proc/self/fd`` takes milliseconds a thousand descriptors).

A pause in which this process and another both stand still shows as one
``lag_max_ms`` the size of the pause with neither ``gc2_s`` nor CPU
seconds behind it.  A process that runs BLS nodes prints its ``BLS
stats:`` line (``telemetry/blsstats.py``) right after this one.
``telemetry/__init__.py`` reads the lag keys of its snapshot from here;
``chipbench/readers/hoststats.py`` reads the line.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import os
import threading
import time

from ..network.pool import CONN_COUNTS
from ..network.wan import WAN_COUNTS
from ..store.engine import WAL_COUNTS
from .blsstats import BLS_COUNTS

log = logging.getLogger(__name__)

LOG_INTERVAL = 5.0
LAG_INTERVAL = 0.05


class HostStats:
    """The process's counters.  ``run`` is the probe and the printer;
    the collector's callback is installed while it runs."""

    def __init__(self):
        self.started = time.monotonic()
        self.lag_samples = 0
        self.lag_total_s = 0.0
        self.lag_max_s = 0.0  # since the start
        self._lag_max_line_s = 0.0  # since the last line
        self.gc2 = 0
        self.gc2_s = 0.0
        self._gc2_t0: float | None = None
        self._loop_clock: int | None = None  # set by ``run`` on the loop

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc2_t0 = time.perf_counter()
        elif self._gc2_t0 is not None:
            self.gc2 += 1
            self.gc2_s += time.perf_counter() - self._gc2_t0
            self._gc2_t0 = None

    def observe_lag(self, lag_s: float) -> None:
        self.lag_samples += 1
        self.lag_total_s += lag_s
        self.lag_max_s = max(self.lag_max_s, lag_s)
        self._lag_max_line_s = max(self._lag_max_line_s, lag_s)

    def lag_json(self) -> dict:
        """The lag keys of the ``Telemetry snapshot:`` document."""
        mean = self.lag_total_s / self.lag_samples if self.lag_samples else 0.0
        return {
            "loop_lag_mean_ms": round(mean * 1e3, 3),
            "loop_lag_max_ms": round(self.lag_max_s * 1e3, 3),
        }

    def line(self, fds: int = 0) -> str:
        """The counters as ``key=value`` pairs, ``fds`` as counted by
        the caller; resets the line's max."""
        # consensus imports telemetry, so its counter is fetched here
        from ..consensus.synchronizer import ANCESTOR_COUNTS

        cpu = os.times()
        mean = self.lag_total_s / self.lag_samples if self.lag_samples else 0.0
        lag_max, self._lag_max_line_s = self._lag_max_line_s, 0.0
        loop_cpu = (
            "" if self._loop_clock is None
            else f"loop_cpu_s={time.clock_gettime(self._loop_clock):.3f} "
        )
        return (
            f"elapsed_s={time.monotonic() - self.started:.3f} "
            f"cpu_user_s={cpu.user:.3f} cpu_sys_s={cpu.system:.3f} "
            f"{loop_cpu}"
            f"lag_samples={self.lag_samples} lag_mean_ms={mean * 1e3:.3f} "
            f"lag_max_ms={lag_max * 1e3:.3f} "
            f"gc2={self.gc2} gc2_s={self.gc2_s:.4f} "
            f"store_appends={WAL_COUNTS.appends} "
            f"store_records={WAL_COUNTS.records} "
            f"ancestor_hits={ANCESTOR_COUNTS.hits} "
            f"ancestor_misses={ANCESTOR_COUNTS.misses} "
            f"sync_requests={ANCESTOR_COUNTS.sync_requests} "
            f"wan_frames={WAN_COUNTS.frames} "
            f"wan_delay_ms={WAN_COUNTS.delay_s * 1e3:.1f} "
            f"wan_base_ms={WAN_COUNTS.base_s * 1e3:.1f} "
            f"conn_opens={CONN_COUNTS.opens} fds={fds}"
        )

    async def run(self, logger=None) -> None:
        """Sample the loop's lag every ``LAG_INTERVAL`` and print the
        line every ``LOG_INTERVAL``; cancelled at shutdown."""
        logger = logger or log
        loop = asyncio.get_running_loop()
        self._loop_clock = time.pthread_getcpuclockid(threading.get_ident())
        next_log = loop.time() + LOG_INTERVAL
        gc.callbacks.append(self._on_gc)
        try:
            while True:
                t0 = loop.time()
                await asyncio.sleep(LAG_INTERVAL)
                now = loop.time()
                self.observe_lag(max(now - t0 - LAG_INTERVAL, 0.0))
                if now >= next_log:
                    next_log = now + LOG_INTERVAL
                    fds = await loop.run_in_executor(None, count_fds)
                    # NOTE: this log entry is scraped (benchmark/scaling.py,
                    # chipbench/readers/hoststats.py)
                    logger.info("Host stats: %s", self.line(fds))
                    if BLS_COUNTS.active:
                        # NOTE: scraped (chipbench/readers/bls.py)
                        logger.info("BLS stats: %s", BLS_COUNTS.line())
        finally:
            gc.callbacks.remove(self._on_gc)


def count_fds() -> int:
    """The process's open file descriptors; 0 where ``/proc`` does not
    list them."""
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return 0


_PROCESS: HostStats | None = None


def process() -> HostStats:
    """The process's one ``HostStats``: a process has one event loop to
    probe, one collector and one CPU account, however many nodes it
    runs."""
    global _PROCESS
    if _PROCESS is None:
        _PROCESS = HostStats()
    return _PROCESS


def start() -> asyncio.Task:
    """Start the process's probe on the running loop (the node CLI's
    entry points call this once; the caller cancels the task)."""
    return asyncio.get_running_loop().create_task(
        process().run(), name="host-stats"
    )


__all__ = [
    "HostStats", "LOG_INTERVAL", "LAG_INTERVAL", "count_fds", "process", "start",
]
