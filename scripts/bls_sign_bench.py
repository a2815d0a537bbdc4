"""Time the native BLS signature: ``crypto/bls/native.py`` ``sign`` (one
``hs_bls_sign`` call: hash to G1, the scalar multiply, compression) over
N seeded (key, 32-byte message) pairs, one call timed at a time, and
print the microseconds a sign, median and p95, with a digest of every
signature made.

    python scripts/bls_sign_bench.py [--n 2000] [--seed 46] [--root DIR]

``--root`` imports the package from another checkout (whose library is
built there on demand), so two trees can be timed on the same pairs in
one process each; equal digests mean the two made the same bytes.  The
result is one JSON line; no benchmark cell runs this script.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pairs(n: int, seed: int, order: int) -> list[tuple[bytes, bytes]]:
    """n seeded (message, key as 32 little-endian bytes), keys in [1, r)."""
    rng = random.Random(seed)
    return [
        (rng.randbytes(32), rng.randrange(1, order).to_bytes(32, "little"))
        for _ in range(n)
    ]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--n", type=int, default=2000, help="signatures timed")
    parser.add_argument("--seed", type=int, default=46)
    parser.add_argument("--root", default=ROOT, help="checkout to import from")
    args = parser.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from hotstuff_tpu.crypto.bls import native
    from hotstuff_tpu.crypto.bls.fields import R

    work = pairs(args.n, args.seed, R)
    for message, key in work[:20]:  # warm the caches and the branch predictor
        native.sign(message, key)
    times_us, digest = [], hashlib.sha256()
    for message, key in work:
        start = time.perf_counter_ns()
        sig = native.sign(message, key)
        times_us.append((time.perf_counter_ns() - start) / 1000)
        if sig is None:
            print(f"refused a key below r: {key.hex()}", file=sys.stderr)
            return 1
        digest.update(sig)
    print(json.dumps({
        "root": root,
        "n": args.n,
        "seed": args.seed,
        "us_median": statistics.median(times_us),
        "us_p95": statistics.quantiles(times_us, n=20)[-1],
        "us_mean": statistics.fmean(times_us),
        "sigs_sha256": digest.hexdigest(),
    }))  # fmt: skip
    return 0


if __name__ == "__main__":
    sys.exit(main())
