"""Probe: is the Pallas DSM kernel latency-bound or throughput-bound in
the lane dimension?

Decides the fate of the 512-lane wide split tile (VERDICT r2 item 1c):
- If a 128-lane tile costs ~the same as a 256-lane tile (latency-bound),
  doubling lanes is ~free and the 512-lane 16-step scan should halve the
  256-vote QC time -> budget the one-time Mosaic compile.
- If cost scales ~linearly with lanes (throughput-bound), the wide tile
  cannot win -> delete it and spend the effort on signed-digit windows.

Method: slope timing (chained dispatches, (T_long-T_short)/delta) of
dual_scalar_mult at batch 128 (bt=128), 256 (bt=256), 512 (bt=256,
grid=2), repeated; reports the median slope per shape.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from hotstuff_tpu.crypto import ed25519_ref as ref
    from hotstuff_tpu.tpu import curve
    from hotstuff_tpu.tpu.pallas_dsm import dual_scalar_mult

    print("platform:", jax.devices()[0].platform, flush=True)

    pk = ref.public_from_seed(b"\x5a" * 32)
    pt = curve.point_to_limbs(ref.point_neg(ref.point_decompress(pk)))
    rng = np.random.default_rng(7)

    def inputs(batch):
        s_win = rng.integers(0, 16, (curve.NWIN, batch)).astype(np.int32)
        k_win = rng.integers(0, 16, (curve.NWIN, batch)).astype(np.int32)
        a = tuple(
            jnp.asarray(np.repeat(np.asarray(c)[None, :], batch, axis=0))
            for c in pt
        )
        return jnp.asarray(s_win), jnp.asarray(k_win), a

    def slope_ms(batch, short=8, long=64, reps=7):
        # long chains: the dispatch latency's variance must be small
        # against (long-short) dispatches of signal, or slopes go
        # negative (observed with 4-vs-16 chains)
        s, k, a = inputs(batch)
        out = dual_scalar_mult(s, k, a)
        jax.block_until_ready(out)  # compile/warm
        slopes = []
        for _ in range(reps):
            times = {}
            for n in (short, long):
                t0 = time.perf_counter()
                for _ in range(n):
                    out = dual_scalar_mult(s, k, a)
                np.asarray(out[1])
                times[n] = time.perf_counter() - t0
            slopes.append((times[long] - times[short]) / (long - short))
        slopes.sort()
        return slopes[len(slopes) // 2] * 1e3

    for batch in (128, 256, 512):
        t0 = time.perf_counter()
        ms = slope_ms(batch)
        print(
            f"batch {batch:4d}: {ms:7.3f} ms/dispatch "
            f"(total incl warm/compile {time.perf_counter() - t0:.1f}s)",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
