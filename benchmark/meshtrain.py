"""Multi-chip mesh wave-train bench (ISSUE 7): sustained train sigs/s
of the PRODUCTION dispatch pipeline over the sharded mesh backend, per
mesh size, plus the scaling-efficiency metric perfgate guards.

Why subprocesses: XLA fixes the device count at first jax import, so a
CPU host cannot re-mesh in-process.  Each mesh size runs in a child
``python -m benchmark.meshtrain --child '<spec>'`` whose environment is
set BEFORE jax loads:

- ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (force_virtual
  — CPU hosts; a real multi-chip host runs with its real devices),
- ``HOTSTUFF_MESH_DEVICES=<m>`` — the production mesh-sizing knob the
  node CLI exposes as ``--mesh-devices``,
- ``HOTSTUFF_WAVE_BUCKETS=<batches>`` — bound the warm set to exactly
  the measured train shapes (each child pays ~2 XLA compiles per batch:
  the psum-word warmup kernel + the dispatch-loop stage kernel),
- ``HOTSTUFF_FORCE_DEVICE_ROUTE=1`` — the cost model must not re-route
  the train to the host path mid-measurement.

The child drives ``LazyDeviceVerifier("mesh")`` through the real
``AsyncVerifyService`` (fixed-shape buckets, dispatch-loop slots,
depth-K pipelining — the same dispatch path production nodes use) and
prints ONE JSON line.  The parent assembles the ``mesh_train`` block:

- ``per_mesh[m].per_batch[b].train_sigs_per_s`` — sustained amortized
  train rate (median-of-reps wall over ``train`` distinct-digest waves),
- ``mesh_scaling_efficiency`` — rate(M) / (M x rate(1)) at the largest
  mesh, best batch (1.0 = perfect linear scale-out; the virtual CPU
  mesh shares one socket, so sub-linear here is expected — the metric
  exists to catch REGRESSIONS in the sharded path, not to prove ICI
  speedup on a laptop).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_MESH_SIZES = (1, 2, 4, 8)
# past-1024 coverage is the point (ISSUE 7): 4096 is the new bucket
DEFAULT_BATCHES = (256, 1024, 4096)
DEFAULT_TRAIN = 4
DEFAULT_REPS = 3
CHILD_TIMEOUT_S = 900.0
VIRTUAL_DEVICES = 8


def _child_env(mesh: int, batches, force_virtual: bool) -> dict:
    env = dict(os.environ)
    if force_virtual:
        kept = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f
        ]
        kept.append(
            f"--xla_force_host_platform_device_count={VIRTUAL_DEVICES}"
        )
        env["XLA_FLAGS"] = " ".join(kept)
        env.setdefault("JAX_PLATFORMS", "cpu")
    env["HOTSTUFF_MESH_DEVICES"] = str(mesh)
    env["HOTSTUFF_WAVE_BUCKETS"] = ",".join(str(b) for b in batches)
    env["HOTSTUFF_FORCE_DEVICE_ROUTE"] = "1"
    return env


def _last_json_line(text: str) -> dict | None:
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def run_child(spec: dict) -> dict:
    """Runs INSIDE the child process (env already pins mesh size,
    buckets, and the device route): sustained wave trains per batch
    through the production async dispatch pipeline."""
    import asyncio

    from benchmark.profile import make_train_claims
    from hotstuff_tpu.crypto.async_service import (
        AsyncVerifyService,
        eval_claims_sync,
    )
    from hotstuff_tpu.node.node import LazyDeviceVerifier

    train = int(spec.get("train", DEFAULT_TRAIN))
    reps = int(spec.get("reps", DEFAULT_REPS))
    batches = tuple(int(b) for b in spec.get("batches", DEFAULT_BATCHES))

    backend = LazyDeviceVerifier("mesh")
    per_batch: dict = {}
    for n in batches:
        claims, pks = make_train_claims(n, train)
        backend.precompute(pks)
        backend.warmup(batch=n)
        # warm the exact train shape through BOTH device entry points:
        # the sync psum-word path (verify_many) and the dispatch-loop
        # stage kernel the service's pipelined slots actually run —
        # batches are buckets, so no measured wave pays a compile
        assert eval_claims_sync(backend.async_backend, [claims[0]]) == [True]
        backend.dispatch_deadline_s = 60.0

        async def drive() -> tuple[list[float], int]:
            svc = AsyncVerifyService(backend, device=True)
            try:
                assert (await svc.verify_claims([claims[0]])) == [True]
                walls: list[float] = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    futs = []
                    for claim in claims:
                        futs.append(
                            asyncio.ensure_future(svc.verify_claims([claim]))
                        )
                        await asyncio.sleep(0)
                        while svc._pending:
                            await asyncio.sleep(0)
                    results = await asyncio.gather(*futs)
                    walls.append(time.perf_counter() - t0)
                    assert all(r == [True] for r in results)
                walls.sort()
                return walls, svc.mesh_dispatches
            finally:
                svc.close()

        walls, mesh_dispatches = asyncio.run(drive())
        wall = walls[len(walls) // 2]
        per_batch[str(n)] = {
            "train_sigs_per_s": round(train * n / wall),
            "wave_p50_ms": round(wall * 1e3 / train, 3),
            "mesh_dispatches": mesh_dispatches,
        }

    device = backend._device
    mesh = getattr(device, "mesh", None)
    return {
        "mesh": int(spec.get("mesh", 0)),
        "mesh_devices": int(mesh.devices.size) if mesh is not None else None,
        "train_waves": train,
        "reps": reps,
        "per_batch": per_batch,
        "train_sigs_per_s": max(
            v["train_sigs_per_s"] for v in per_batch.values()
        ),
    }


def run_sharded_child() -> dict:
    """Child body for the virtual-mesh ``sharded_route`` re-measure
    (ISSUE 7 satellite): bench.py's own sharded-route probe, but on the
    forced 8-device virtual mesh so CPU hosts stop reporting
    ``mesh_devices: 1``."""
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    import bench

    msgs, pks, sigs = bench.make_qc_batch(256)
    doc = bench.bench_sharded(msgs, pks, sigs)
    doc["virtual_host_devices"] = VIRTUAL_DEVICES
    return doc


def run_sharded_virtual(timeout_s: float = CHILD_TIMEOUT_S) -> dict | None:
    """Parent-side: run the sharded-route probe on the virtual mesh.
    Returns None on any child failure (the caller keeps its in-process
    measurement)."""
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmark.meshtrain", "--child-sharded"],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=_child_env(VIRTUAL_DEVICES, DEFAULT_BATCHES, True),
            cwd=REPO_ROOT,
        )
    except (subprocess.TimeoutExpired, OSError):
        return None
    if proc.returncode != 0:
        return None
    return _last_json_line(proc.stdout)


def run_mesh_train(
    mesh_sizes=DEFAULT_MESH_SIZES,
    batches=DEFAULT_BATCHES,
    train: int = DEFAULT_TRAIN,
    reps: int = DEFAULT_REPS,
    force_virtual: bool = True,
) -> dict:
    """Parent: one child per mesh size, then the efficiency rollup.

    ``force_virtual=False`` on a real multi-chip host (the children then
    mesh over the real devices via HOTSTUFF_MESH_DEVICES alone)."""
    per_mesh: dict = {}
    errors: dict = {}
    spec_base = {"batches": list(batches), "train": train, "reps": reps}
    for m in mesh_sizes:
        spec = dict(spec_base, mesh=m)
        cmd = [
            sys.executable,
            "-m",
            "benchmark.meshtrain",
            "--child",
            json.dumps(spec),
        ]
        try:
            proc = subprocess.run(
                cmd,
                capture_output=True,
                text=True,
                timeout=CHILD_TIMEOUT_S,
                env=_child_env(m, batches, force_virtual),
                cwd=REPO_ROOT,
            )
        except subprocess.TimeoutExpired:
            errors[str(m)] = f"timeout after {CHILD_TIMEOUT_S:.0f}s"
            continue
        doc = _last_json_line(proc.stdout)
        if proc.returncode != 0 or doc is None:
            errors[str(m)] = (
                f"rc={proc.returncode}: {proc.stderr.strip()[-400:]}"
            )
            continue
        per_mesh[str(m)] = doc

    out: dict = {
        "mesh_sizes": list(mesh_sizes),
        "batches": list(batches),
        "train_waves": train,
        "force_virtual": bool(force_virtual),
        "per_mesh": per_mesh,
    }
    if errors:
        out["errors"] = errors

    # efficiency vs the smallest measured mesh (normally 1): best batch,
    # because small batches under-fill large meshes by construction
    base_m = min((int(k) for k in per_mesh), default=None)
    if base_m is not None:
        base = per_mesh[str(base_m)]["per_batch"]
        eff_per_mesh: dict = {}
        for m_str, doc in per_mesh.items():
            scale = int(m_str) / base_m
            effs = [
                v["train_sigs_per_s"]
                / (scale * base[b]["train_sigs_per_s"])
                for b, v in doc["per_batch"].items()
                if base.get(b, {}).get("train_sigs_per_s")
            ]
            if effs:
                eff_per_mesh[m_str] = round(max(effs), 4)
        out["scaling_efficiency_per_mesh"] = eff_per_mesh
        top = str(max(int(k) for k in per_mesh))
        if top in eff_per_mesh and int(top) > base_m:
            out["mesh_scaling_efficiency"] = eff_per_mesh[top]
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="mesh wave-train scaling bench (ISSUE 7)"
    )
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument(
        "--child-sharded", action="store_true", help=argparse.SUPPRESS
    )
    ap.add_argument("--mesh-sizes", default=None, help="e.g. 1,2,4,8")
    ap.add_argument("--batches", default=None, help="e.g. 256,1024,4096")
    ap.add_argument("--train", type=int, default=DEFAULT_TRAIN)
    ap.add_argument("--reps", type=int, default=DEFAULT_REPS)
    ap.add_argument(
        "--real-devices",
        action="store_true",
        help="mesh over the host's real accelerators instead of the "
        "virtual CPU mesh",
    )
    args = ap.parse_args(argv)

    if args.child is not None:
        print(json.dumps(run_child(json.loads(args.child))))
        return 0
    if args.child_sharded:
        print(json.dumps(run_sharded_child()))
        return 0

    kw: dict = {"train": args.train, "reps": args.reps}
    if args.mesh_sizes:
        kw["mesh_sizes"] = tuple(
            int(x) for x in args.mesh_sizes.split(",") if x
        )
    if args.batches:
        kw["batches"] = tuple(int(x) for x in args.batches.split(",") if x)
    print(json.dumps(run_mesh_train(force_virtual=not args.real_devices, **kw)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
