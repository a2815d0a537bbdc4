#!/usr/bin/env python
"""The LINT=1 gate: static analysis plane + native sanitizer smoke.

Three stages, all must pass:

1. ``python -m hotstuff_tpu.analysis check`` — every lint rule
   (no-blocking-in-async, wire-decoder-bounds, taxonomy-registry,
   env-knob-registry, guarded-by) over the tree, inline allows and the
   committed allowlist applied.
2. ``gen-knobs --check`` — docs/KNOBS.md freshness (also surfaced as a
   rule finding; repeated here so the failure message names the fix).
3. ``scripts/san_check.py`` — the TSan/ASan reactor + store stress,
   skip-if-unsupported.

Runs stdlib-only (no jax import), so the CI lint job needs no heavy
deps.  Invoked as ``LINT=1 scripts/trace.sh`` to mirror the BYZ=/
STATE= gate pattern.
"""

from __future__ import annotations

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stage(title: str, argv: list) -> bool:
    print(f"== {title} ==")
    proc = subprocess.run(argv, cwd=ROOT)
    print()
    return proc.returncode == 0


def main() -> int:
    py = sys.executable
    ok = True
    ok &= stage(
        "static analysis rules",
        [py, "-m", "hotstuff_tpu.analysis", "check"],
    )
    ok &= stage(
        "env-knob registry freshness",
        [py, "-m", "hotstuff_tpu.analysis", "gen-knobs", "--check"],
    )
    ok &= stage(
        "native sanitizer smoke",
        [py, os.path.join(ROOT, "scripts", "san_check.py")],
    )
    if not ok:
        print("ANALYSIS CHECK FAIL")
        return 1
    print("ANALYSIS CHECK OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
