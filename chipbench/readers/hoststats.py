"""The process's own account of what it cost the host: the ``Host
stats:`` line (``hotstuff_tpu/telemetry/hoststats.py``) the committee
prints every 5 s, tracing on or off.

``chipbench/logs.py`` keeps no line it does not know, so this reads
``node.log`` in the run directory itself.  Counters are cumulative: the
window's share is the last line at or before its end less the last one
at or before its start.  ``lag_max_ms`` alone is of the 5 s before its
line, so the window's is the largest line in it.  A program that prints
no such line (a parent commit) gives None.
"""

from __future__ import annotations

import calendar
import os
import time

from ..logs import RE_LINE
from ..reduce import Run
from .hostspans import run_dir_of

MARK = "Host stats: "


def lines_of(text: str) -> list[tuple[float, dict[str, float]]]:
    """``(stamp, counters)`` of every ``Host stats`` line in a log."""
    out = []
    for line in text.splitlines():
        at = line.find(MARK)
        m = RE_LINE.match(line) if at >= 0 else None
        if m is None:
            continue
        try:
            counters = {
                k: float(v)
                for k, v in (
                    item.split("=") for item in line[at + len(MARK):].split()
                )
            }
        except ValueError:
            continue
        second = time.strptime(m.group(1), "%Y-%m-%dT%H:%M:%S")
        out.append(
            (calendar.timegm(second) + int(m.group(2)) / 1000.0, counters)
        )
    return out


def _lines(run: Run):
    if not hasattr(run, "_host_stats"):
        run._host_stats = []
        run_dir = run_dir_of(run)
        if run_dir is not None:
            try:
                with open(os.path.join(run_dir, "node.log"), "rb") as f:
                    text = f.read().decode("utf-8", "replace")
                run._host_stats = lines_of(text)
            except OSError:
                pass
    return run._host_stats


def window_delta(lines, t0: float, t1: float) -> dict[str, float] | None:
    """Last line at or before ``t1`` less the last at or before ``t0``
    (the first line, if none is that early), with ``wall_s`` between
    their stamps."""
    upto = [(s, c) for s, c in lines if s <= t1]
    if len(upto) < 2:
        return None
    before = [(s, c) for s, c in upto if s <= t0] or upto[:1]
    (s0, c0), (s1, c1) = before[-1], upto[-1]
    if s1 <= s0:
        return None
    delta = {k: c1[k] - c0.get(k, 0.0) for k in c1}
    delta["wall_s"] = s1 - s0
    return delta


def cpu_share(run: Run):
    """The process's CPU seconds (user + system, all threads) over the
    wall time of the window: 100% is one core kept busy."""
    d = window_delta(_lines(run), run.t0, run.t1)
    if d is None or "cpu_user_s" not in d:
        return None
    return 100.0 * (d["cpu_user_s"] + d["cpu_sys_s"]) / d["wall_s"]


def loop_lag_max_ms(run: Run):
    """The largest ``lag_max_ms`` of the lines printed in the window: a
    50 ms sleep woke that late at worst."""
    inside = [
        c["lag_max_ms"] for s, c in _lines(run)
        if run.t0 < s <= run.t1 and "lag_max_ms" in c
    ]
    return max(inside, default=None)


def gc_pause_ms(run: Run):
    """Seconds the collector's generation-2 passes took in the window."""
    d = window_delta(_lines(run), run.t0, run.t1)
    if d is None or "gc2_s" not in d:
        return None
    return 1e3 * d["gc2_s"]
