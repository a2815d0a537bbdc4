"""The digest relay, from the ``Proposer stats:`` line
(``hotstuff_tpu/consensus/proposer.py``) every node prints every 5 s,
tracing on or off.

Counters are cumulative and a node's own (the logger's name ends in the
node's name), so a node's share of the window is its last line at or
before the window's end less its last at or before the start, as for
``Host stats`` (``hoststats.window_delta``), and a metric sums the
nodes.  ``chipbench/logs.py`` keeps no line it does not know, so this
reads ``node.log`` in the run directory itself.  A program that prints
no such line (a parent commit) gives None.
"""

from __future__ import annotations

import calendar
import os
import time

from ..logs import RE_LINE
from ..reduce import Run
from .hostspans import run_dir_of
from .hoststats import window_delta

MARK = "Proposer stats: "


def lines_of(text: str) -> dict[str, list[tuple[float, dict[str, float]]]]:
    """``node -> [(stamp, counters)]`` of every ``Proposer stats``
    line in a log."""
    out: dict[str, list] = {}
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
        out.setdefault(m.group(3).rsplit(".", 1)[-1], []).append(
            (calendar.timegm(second) + int(m.group(2)) / 1000.0, counters)
        )
    return out


def _lines(run: Run):
    if not hasattr(run, "_proposer_stats"):
        run._proposer_stats = {}
        run_dir = run_dir_of(run)
        if run_dir is not None:
            try:
                with open(os.path.join(run_dir, "node.log"), "rb") as f:
                    text = f.read().decode("utf-8", "replace")
                run._proposer_stats = lines_of(text)
            except OSError:
                pass
    return run._proposer_stats


def window_sum(run: Run) -> dict[str, float] | None:
    """The window's counters, summed over the nodes that printed at
    least two lines by its end."""
    total: dict[str, float] = {}
    for lines in _lines(run).values():
        delta = window_delta(lines, run.t0, run.t1)
        if delta is None:
            continue
        for k, v in delta.items():
            total[k] = total.get(k, 0.0) + v
    return total or None


def relay_hit_share(run: Run):
    """Payloads proposed in the window by a node that is not their
    home, over all payloads proposed."""
    d = window_sum(run)
    if d is None or "proposed_relayed" not in d:
        return None
    proposed = d["proposed_relayed"] + d["proposed_home"]
    return 100.0 * d["proposed_relayed"] / proposed if proposed else None


def payload_wait_ms(run: Run):
    """Mean time from a payload's admission at its home to the home
    seeing it in a processed block, over those first seen in the
    window."""
    d = window_sum(run)
    if d is None or not d.get("wait_n"):
        return None
    return d["wait_ms_sum"] / d["wait_n"]
