"""What one run leaves behind, as the readers see it."""

from __future__ import annotations

import bisect
import math
from dataclasses import dataclass, field

from .gen import Plan
from .logs import CommitteeLog


#: the log stamps a commit in whole milliseconds, cut and not rounded
STAMP_MS = 1.0


def percentile(
    values: list[float], share: float, bin_width: float = 0.0
) -> float | None:
    """Nearest rank: the smallest value with at least ``share`` of the
    sample at or below it.  Where the values are known only to a bin
    (``bin_width``: a latency ends at a stamp cut to the millisecond, so
    the true value lies in ``[v, v + bin_width)``), the result is
    interpolated inside the bin the rank falls in, as for any binned
    sample: at 1,000 tx/s every due time has the same fraction of a
    millisecond, all latencies of a run lie on one 1 ms grid, and the
    bare nearest rank would move a whole millisecond at a time."""
    if not values:
        return None
    ordered = sorted(values)
    rank = share * len(ordered)
    value = ordered[max(0, math.ceil(rank) - 1)]
    if bin_width <= 0:
        return value
    lo = bisect.bisect_left(ordered, value - bin_width / 2)
    hi = bisect.bisect_right(ordered, value + bin_width / 2)
    return value + bin_width * min(1.0, max(0.0, (rank - lo) / (hi - lo)))


@dataclass
class Run:
    """A finished run: the plan, what the generator did with it, the
    committee's log and the phases, all on the host's wall clock."""

    config: dict
    traffic: dict
    plan: Plan
    log: CommitteeLog
    sent_at: list
    refused: set
    t_ramp: float
    t_end: float = 0.0
    setup: dict = field(default_factory=dict)
    trace: dict | None = None
    _commit_at: dict = field(default_factory=dict)

    @property
    def quorum(self) -> int:
        return self.config["guarantees"]["quorum"]

    @property
    def t0(self) -> float:
        """The start of the measured window."""
        return self.t_ramp + self.plan.ramp_s

    @property
    def t1(self) -> float:
        return self.t0 + self.plan.seconds

    def due(self, k: int) -> float:
        return self.t_ramp + self.plan.due_s[k]

    def commit_at(self, k: int) -> float | None:
        """When payload ``k`` was committed on a quorum; None if it was
        refused or is not committed on one by the end of the run."""
        if k not in self._commit_at:
            self._commit_at[k] = (
                None
                if k in self.refused
                else self.log.payload_commit(self.plan.ids[k], self.quorum)
            )
        return self._commit_at[k]

    def window_latencies_ms(self) -> list[float]:
        """Due time to quorum commit for every payload due in the
        window.  One that was not committed by the end counts as the
        longest latency the run could have seen, so it lies beyond
        every percentile that the committed ones reach."""
        beyond = (self.t_end - self.t0) * 1e3
        out = []
        for k in self.plan.window():
            at = self.commit_at(k)
            out.append(beyond if at is None else (at - self.due(k)) * 1e3)
        return out

    def blocks_in_window(self) -> list[tuple[float, str, int, list[str]]]:
        """The blocks made inside the window, in the order made."""
        return sorted(
            (made, block, rnd, ids)
            for block, (made, _node, rnd, ids) in self.log.created.items()
            if self.t0 <= made < self.t1
        )
