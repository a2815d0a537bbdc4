"""Compile-probe: which fused-kernel tile shapes fit the scoped VMEM
limit on the real TPU (the wave-batched kernel's transients tripled the
per-tile footprint: batch-1024 @ bt=256 OOMed at 21.7M vs the 16M cap).

Tries the fused unsplit kernel at bt=128/256 and the fused split kernel
at tile 256, reporting compile success/OOM + a quick slope timing for
the ones that fit.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from hotstuff_tpu.crypto import ed25519_ref as ref
    from hotstuff_tpu.tpu.ed25519 import BatchVerifier

    print("platform:", jax.devices()[0].platform, flush=True)

    def items(n):
        seed = b"\x5a" * 32
        msg = b"probe"
        pk = ref.public_from_seed(seed)
        sig = ref.sign(seed, msg)
        return [msg] * n, [pk] * n, [sig] * n

    v = BatchVerifier(min_device_batch=0)

    # split kernel shape: n <= SPLIT_MAX -> rows 2n, tile 256
    for label, n in (("split/tile256 (64 sigs)", 64),
                     ("unsplit/bt256 (256 sigs)", 256),
                     ("unsplit/bt256 (1024 batch)", 1024)):
        t0 = time.perf_counter()
        try:
            out = v.verify(*items(n))
            ok = bool(np.asarray(out).all())
            print(f"{label}: OK valid={ok} "
                  f"({time.perf_counter() - t0:.1f}s)", flush=True)
        except Exception as e:
            msg = str(e)
            brief = "VMEM OOM" if "vmem" in msg.lower() else msg[:160]
            print(f"{label}: FAIL {brief} "
                  f"({time.perf_counter() - t0:.1f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
