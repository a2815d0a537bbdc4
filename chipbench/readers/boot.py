"""Where a committee's boot goes, from the ``Boot stats:`` line
``node run-many`` prints once, when every node is up
(``hotstuff_tpu/node/main.py``): ``keys_s``, the committee's keys decoded
and its proofs of possession checked, once for the process; ``nodes_s``,
``Node.new`` for every node less the verifier's warm-up; ``warm_s``, the
warm-up.  The rest of ``setup.boot_s`` is the interpreter, the imports,
the chip's start and the wait for the first commit.  A program that
prints no such line (a parent commit) gives None."""

from __future__ import annotations

import os
import re

from ..reduce import Run
from .hostspans import run_dir_of

RE_BOOT = re.compile(r"Boot stats: (.*)")


def stats_of(text: str) -> dict[str, float] | None:
    """The counters of the first ``Boot stats`` line in a log."""
    m = RE_BOOT.search(text)
    if m is None:
        return None
    try:
        return {
            k: float(v) for k, v in (item.split("=") for item in m.group(1).split())
        }
    except ValueError:
        return None


def boot_stats(run: Run) -> dict[str, float] | None:
    if not hasattr(run, "_boot_stats"):
        run._boot_stats = None
        run_dir = run_dir_of(run)
        if run_dir is not None:
            try:
                with open(os.path.join(run_dir, "node.log"), "rb") as f:
                    run._boot_stats = stats_of(f.read().decode("utf-8", "replace"))
            except OSError:
                pass
    return run._boot_stats


def keys_s(run: Run):
    stats = boot_stats(run)
    return None if stats is None else stats.get("keys_s")


def nodes_s(run: Run):
    stats = boot_stats(run)
    return None if stats is None else stats.get("nodes_s")
