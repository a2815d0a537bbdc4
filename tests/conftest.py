"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding
(`shard_map` over a Mesh) is exercised without TPU hardware.  The
platform and device count are pinned through jax.config before any
backend is touched (and through the environment, for the subprocesses
tests start).  Runs on the chip go through `chip_smoke.py`.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-scenario committee runs excluded from the tier-1 "
        "sweep (-m 'not slow')",
    )


@pytest.fixture(params=["wal", "native"])
def engine_cls(request):
    """Each store engine's class in turn; the native one is skipped
    where its library cannot be built."""
    if request.param == "wal":
        from hotstuff_tpu.store.engine import WalEngine

        return WalEngine
    try:
        from hotstuff_tpu.store.native import NativeEngine
    except (ImportError, OSError):  # no compiler in this environment
        pytest.skip("native lib not built")
    return NativeEngine
