"""The committee's log, read as it grows.

All nodes of a ``run-many`` committee write one file; the logger's name
ends in the node's name (``hotstuff_tpu.consensus.core.v+40KYft``), so
every node's commits can be told apart.  The regexes are those of
``benchmark/logs.py``; what is computed from them is not: latency is
taken per payload, to the commit on a quorum of nodes, and that parser
takes it from one sample payload a burst to the earliest commit on any
node.  The child runs with ``TZ=UTC``, so a stamp is UTC.
"""

from __future__ import annotations

import calendar
import json
import re
import time

RE_LINE = re.compile(
    r"(\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2})\.(\d{3})Z \[\w+\] (\S+) (.*)"
)
RE_CREATED = re.compile(r"Created block (\d+) \(payloads (\S*)\) -> (\S+)")
RE_COMMITTED = re.compile(r"Committed block (\d+) -> (\S+)")
RE_TIMEOUT = re.compile(r"Timeout reached for round (\d+)")
RE_STATS = re.compile(r"Verify service stats \[(\S+)\]: (.*)")
RE_WARM = re.compile(r"Device verifier \[(\S+)\] warm in ([\d.]+) s: (\{.*\})")


class CommitteeLog:
    """Everything the benchmark reads from the committee's log."""

    def __init__(self):
        self._offset = 0
        self._second = ("", 0)
        # block -> (stamp, node, round, payload ids), as its leader made it
        self.created: dict[str, tuple[float, str, int, list[str]]] = {}
        # payload id -> the blocks that carry it (an orphaned proposal's
        # payloads are proposed again, so there can be two)
        self.blocks_of: dict[str, list[str]] = {}
        # block -> node -> (stamp, round): each node's own commit
        self.commits: dict[str, dict[str, tuple[float, int]]] = {}
        # node -> [(round, block)] in the order the node committed them
        self.chain: dict[str, list[tuple[int, str]]] = {}
        self.timeouts: list[tuple[float, str, int]] = []
        # (stamp, tag, counters) of every 'Verify service stats' line
        self.stats: list[tuple[float, str, dict[str, float]]] = []
        self.warm: tuple[float, dict] | None = None
        self.tracebacks = 0
        self.first_commit: float | None = None

    def _stamp(self, second: str, millis: str) -> float:
        if second != self._second[0]:
            self._second = (
                second,
                calendar.timegm(time.strptime(second, "%Y-%m-%dT%H:%M:%S")),
            )
        return self._second[1] + int(millis) / 1000.0

    def poll(self, path: str) -> None:
        """Read what the file has gained since the last call."""
        try:
            with open(path, "rb") as f:
                f.seek(self._offset)
                data = f.read()
        except OSError:
            return
        end = data.rfind(b"\n") + 1
        self._offset += end
        self.feed(data[:end].decode("utf-8", "replace"))

    def feed(self, text: str) -> None:
        for line in text.splitlines():
            if "Traceback (most recent call last)" in line:
                self.tracebacks += 1
                continue
            m = RE_LINE.match(line)
            if m is None:
                continue
            second, millis, logger, message = m.groups()
            node = logger.rsplit(".", 1)[-1]
            if message.startswith("Committed block"):
                rnd, block = RE_COMMITTED.match(message).groups()
                stamp = self._stamp(second, millis)
                self.commits.setdefault(block, {}).setdefault(
                    node, (stamp, int(rnd))
                )
                self.chain.setdefault(node, []).append((int(rnd), block))
                if self.first_commit is None:
                    self.first_commit = stamp
            elif message.startswith("Created block"):
                rnd, payloads, block = RE_CREATED.match(message).groups()
                ids = payloads.split(",") if payloads else []
                self.created[block] = (
                    self._stamp(second, millis), node, int(rnd), ids
                )
                for pid in ids:
                    self.blocks_of.setdefault(pid, []).append(block)
            elif message.startswith("Timeout reached"):
                rnd = RE_TIMEOUT.match(message).group(1)
                self.timeouts.append(
                    (self._stamp(second, millis), node, int(rnd))
                )
            elif message.startswith("Verify service stats"):
                tag, rest = RE_STATS.match(message).groups()
                counters = {
                    k: float(v)
                    for k, v in (item.split("=") for item in rest.split())
                }
                self.stats.append((self._stamp(second, millis), tag, counters))
            elif message.startswith("Device verifier") and " warm in " in message:
                m = RE_WARM.match(message)
                if m is not None:
                    self.warm = (float(m.group(2)), json.loads(m.group(3)))

    def quorum_commit(self, block: str, quorum: int) -> float | None:
        """When the ``quorum``-th node committed ``block``."""
        stamps = sorted(s for s, _ in self.commits.get(block, {}).values())
        return stamps[quorum - 1] if len(stamps) >= quorum else None

    def payload_commit(self, pid: str, quorum: int) -> float | None:
        """When payload ``pid`` was committed on a quorum, if it was."""
        for block in self.blocks_of.get(pid, ()):
            stamp = self.quorum_commit(block, quorum)
            if stamp is not None:
                return stamp
        return None

    def stats_at(self, t: float) -> dict[str, float] | None:
        """The verify services' counters as last printed at or before
        ``t``, summed over the services; None if none had printed."""
        last: dict[str, dict[str, float]] = {}
        for stamp, tag, counters in self.stats:
            if stamp <= t:
                last[tag] = counters
        if not last:
            return None
        total: dict[str, float] = {}
        for counters in last.values():
            for k, v in counters.items():
                total[k] = total.get(k, 0.0) + v
        return total
