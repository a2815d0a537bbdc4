"""The device's G1 sums against the plain reference, at the BLS cells'
shapes: ``TpuG1RunningSum`` over 171, 64 and 43 seeded vote signatures
(the quorum of a 256-node committee, a full 64-node committee's votes
and its quorum), each compared byte for byte with
``crypto/bls_g1_ref.py``; and the running add's two formulations
on the device itself, the Pallas kernel against the XLA program, limb
for limb along chains of seeded adds (the kernel interpreted on the
CPU).  Then a short profiler trace of
eight running-sum adds, read back the way ``chipbench/readers/bls.py``
reads a cell's: the running-sum program's executions and their
operations.

    python scripts/bls_device_check.py

It prints the platform beside every result and writes
``chiprun_out/bls_device_check/result.json``; exit 1 when a sum differs
from the reference.  On the CPU (``JAX_PLATFORMS=cpu``) it is a rehearsal:
the trace has no device plane there.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def seeded_votes(n: int) -> list[bytes]:
    from hotstuff_tpu.crypto.bls import BlsSecretKey
    from hotstuff_tpu.crypto.scheme import bls_keygen

    digest = hashlib.sha256(b"bls_device_check vote").digest()
    out = []
    for i in range(n):
        _, secret = bls_keygen(b"bls_device_check keys", i)
        sk = BlsSecretKey(int.from_bytes(secret, "big"))
        out.append(sk.sign(digest).to_bytes())
    return out


def kernels_agree(points) -> bool:
    """``_running_add_pallas`` and ``_running_add_xla``, each jitted for
    the default device, give the same limbs after every add of the
    points, in four orders."""
    import random
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hotstuff_tpu.tpu import bls as T

    interpret = jax.default_backend() != "tpu"  # the CPU rehearsal
    xla = jax.jit(T._running_add_xla)
    pallas = jax.jit(partial(T._running_add_pallas, interpret=interpret))
    z = np.zeros((1, T.NLIMBS), np.int32)
    z[0, 0] = 1
    for seed in range(4):
        order = random.Random(seed).sample(points, len(points))
        a = b = (
            jnp.zeros((1, T.NLIMBS), jnp.int32),
            jnp.asarray(T.to_mont_limbs(1), jnp.int32).reshape(1, T.NLIMBS),
            jnp.zeros((1, T.NLIMBS), jnp.int32),
        )
        for pt in order:
            row = (T.ints_to_limbs_batch([pt.x]), T.ints_to_limbs_batch([pt.y]), z)
            a, b = xla(*a, *row), pallas(*b, *row)
            if not all(np.array_equal(u, v) for u, v in zip(a, b)):
                return False
    return True


def main() -> int:
    import jax

    from chipbench.readers import bls as bls_reader
    from hotstuff_tpu.crypto import bls_g1_ref as ref
    from hotstuff_tpu.crypto.bls.curve import G1Point
    from hotstuff_tpu.tpu import device_info
    from hotstuff_tpu.tpu.bls import TpuG1RunningSum

    device = device_info()
    votes = seeded_votes(171)
    points = [G1Point.from_bytes(v, subgroup_check=False) for v in votes]
    checks = {}
    for n in (171, 64, 43):
        acc = TpuG1RunningSum()
        t0 = time.perf_counter()
        for pt in points[:n]:
            acc.add(pt)
        got = acc.snapshot().to_bytes()
        checks[f"running_sum_{n}"] = {
            "equal": got == ref.sum_compressed(votes[:n]),
            "seconds": time.perf_counter() - t0,
        }
    checks["pallas_vs_xla"] = {"equal": kernels_agree(points[:64])}

    trace_dir = tempfile.mkdtemp(prefix="bls_device_check_")
    acc = TpuG1RunningSum()
    acc.add(points[0])
    acc.snapshot()  # compiled before the trace starts
    jax.profiler.start_trace(trace_dir)
    acc = TpuG1RunningSum()
    for pt in points[:8]:
        acc.add(pt)
    traced_equal = acc.snapshot().to_bytes() == ref.sum_compressed(votes[:8])
    jax.profiler.stop_trace()
    events = bls_reader.trace_events(trace_dir)
    modules = [m for m in events["modules"] if bls_reader.RUNNING_ADD in m[0]]
    ops_per_add = [
        sum(start <= o[1] < start + length for o in events["ops"])
        for _name, start, length in modules
    ]
    reduced = bls_reader.reduce({
        "loop": [["proposer.make", 0, 1, {"round": 1}],
                 ["agg.accumulate", 0, 1, {}]],
        "modules": events["modules"], "ops": events["ops"],
    })  # fmt: skip
    result = {
        "device": device,
        "checks": checks,
        "trace": {
            "equal": traced_equal,
            "running_add_executions": len(modules),
            "running_add_us": reduced["running_add_us"] if reduced else None,
            "ops_per_add": ops_per_add,
        },
    }
    out_dir = os.path.join(ROOT, "chiprun_out", "bls_device_check")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"device": device, "checks": checks,
                      "running_add_executions": len(modules),
                      "running_add_us": result["trace"]["running_add_us"],
                      "ops_per_add": ops_per_add}))
    ok = traced_equal and all(c["equal"] for c in checks.values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
