"""The comparison that decides ``correct``.

The plain reference of a replicated log is its definition: every node
holds the same block at every height it has committed, in order, and
holds each payload once.  ``benchmark/invariants.py`` states the first
for chaos runs; this is what a benchmark run needs of it, over the
commits read back from every node's own log lines after the run.
"""

from __future__ import annotations

from .logs import CommitteeLog


def violations(log: CommitteeLog, nodes: int) -> list[str]:
    """What the committed chains say against the guarantees; an empty
    list where they hold."""
    found: list[str] = []
    if len(log.chain) != nodes:
        found.append(
            f"{len(log.chain)} of {nodes} nodes committed anything at all"
        )
    at_round: dict[int, tuple[str, str]] = {}
    for node in sorted(log.chain):
        last = 0
        for rnd, block in log.chain[node]:
            if rnd <= last:
                found.append(
                    f"{node} committed round {rnd} after round {last}"
                )
            last = rnd
            first = at_round.setdefault(rnd, (block, node))
            if first[0] != block:
                found.append(
                    f"round {rnd}: {first[1]} holds {first[0]}, "
                    f"{node} holds {block}"
                )
    for pid, blocks in log.blocks_of.items():
        committed = [b for b in blocks if b in log.commits]
        if len(committed) > 1:
            found.append(
                f"payload {pid} committed in {len(committed)} blocks: "
                + ", ".join(committed)
            )
    return found[:20]
