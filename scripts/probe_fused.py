"""Focused device timing: DSM-only vs fused verify at 128/256/1024
lanes, long-chain slope + median, one quiet process.

Separates per-tile scan cost from the fused epilogue cost and
cross-checks the grid scaling (batch 256 = 2 tiles must cost ~2x one
128-lane tile; divergence means the measurement, not the kernel)."""

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
    from hotstuff_tpu.tpu import pallas_dsm
    from hotstuff_tpu.tpu.ed25519 import _bytes_to_windows_msb

    print("platform:", jax.devices()[0].platform, flush=True)
    rng = np.random.default_rng(11)
    pk = ref.public_from_seed(b"\x5a" * 32)
    pt = curve.point_to_limbs(ref.point_neg(ref.point_decompress(pk)))

    def inputs(batch):
        s_rows = rng.integers(0, 256, (batch, 32)).astype(np.uint8)
        s_rows[:, 31] &= 0x0F  # keep scalars < 2^252 (window form only)
        k_rows = rng.integers(0, 256, (batch, 32)).astype(np.uint8)
        k_rows[:, 31] &= 0x0F
        s_win = jnp.asarray(_bytes_to_windows_msb(s_rows).T)
        k_win = jnp.asarray(_bytes_to_windows_msb(k_rows).T)
        a = tuple(
            jnp.asarray(np.repeat(np.asarray(c)[None, :], batch, axis=0))
            for c in pt
        )
        r_y = jnp.asarray(rng.integers(0, 1 << 13, (batch, 20)).astype(np.int32))
        r_sign = jnp.asarray(rng.integers(0, 2, batch).astype(np.int32))
        return s_win, k_win, a, r_y, r_sign

    def slope_ms(fn, fetch, short=8, long=64, reps=7):
        out = fn()
        jax.block_until_ready(out)
        slopes = []
        for _ in range(reps):
            times = {}
            for n in (short, long):
                t0 = time.perf_counter()
                for _ in range(n):
                    out = fn()
                fetch(out)
                times[n] = time.perf_counter() - t0
            slopes.append((times[long] - times[short]) / (long - short))
        slopes.sort()
        return slopes[len(slopes) // 2] * 1e3

    for batch in (128, 256, 1024):
        s_win, k_win, a, r_y, r_sign = inputs(batch)
        dsm = slope_ms(
            lambda: pallas_dsm.dual_scalar_mult(s_win, k_win, a),
            lambda o: np.asarray(o[1]),
        )
        fused = slope_ms(
            lambda: pallas_dsm.verify_compressed(s_win, k_win, a, r_y, r_sign),
            lambda o: np.asarray(o),
        )
        print(
            f"batch {batch:4d}: dsm {dsm:7.3f} ms  fused {fused:7.3f} ms  "
            f"(epilogue {fused - dsm:+.3f})",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
