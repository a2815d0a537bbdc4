"""hotstuff_tpu.analysis — the consensus-aware static analysis plane.

A custom AST lint framework (stdlib ``ast`` + ``tokenize``, zero
third-party deps) whose rules encode this codebase's load-bearing
conventions instead of generic style:

- **no-blocking-in-async** — no ``time.sleep`` / ``Future.result()`` /
  ``block_until_ready`` / synchronous store or socket calls lexically
  inside ``async def`` bodies (``consensus/``, ``network/``, ``node/``):
  a blocking call on the event loop stalls the pacemaker and breaks the
  honest-node timeliness assumption of the trusted-subset regime.
- **wire-decoder-bounds** — every length/count a wire decoder reads must
  pass an ordering comparison before it drives a slice or a decode loop
  (``consensus/wire.py``, ``consensus/messages.py``), so a new frame tag
  cannot ship the allocation-bomb bug class the fuzz corpus only catches
  after the fact.
- **taxonomy-registry** — journal edge names and verify-pipeline span
  stage names must come from ``telemetry/taxonomy.py`` (which
  ``benchmark/traces.py`` also renders from): an unregistered edge is a
  lint error, not a silently-empty Perfetto track.
- **env-knob-registry** — every ``HOTSTUFF_*`` knob the code reads must
  appear in the generated ``docs/KNOBS.md`` (kept fresh by this rule).
- **guarded-by** — fields touched from both a dispatch-loop thread and
  the asyncio loop must carry a ``# guarded-by: <lock>`` annotation; a
  lockset walker checks annotated locks are actually held at writes.
- **no-await-in-span** — no ``await`` / ``async for`` / ``async with`` /
  ``yield`` lexically inside a ``with ...span(...)`` block
  (``consensus/``, ``network/``, ``node/``, ``crypto/``, ``store/``): a
  span lands in the profiler's trace, whose events on one thread must
  nest, and 64 cores interleave on the loop thread at every ``await``.

Escape hatches, in preference order: fix the finding; suppress one site
with ``# lint: allow(<rule>)  -- <why>`` on (or directly above) the
flagged line; grandfather it in ``analysis/allowlist.txt`` (one
``rule:path:code`` key per line — the list is committed and expected to
stay empty or justified).

CLI::

    python -m hotstuff_tpu.analysis check [--json]
    python -m hotstuff_tpu.analysis gen-knobs [--check]

The repo gate is ``LINT=1 scripts/trace.sh`` (scripts/analysis_check.py:
all rules + KNOBS freshness + the native sanitizer smoke).
"""

from .framework import Finding, SourceFile, load_allowlist, run_rules

__all__ = ["Finding", "SourceFile", "load_allowlist", "run_rules"]
