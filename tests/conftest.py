"""Test fixture: force an 8-device virtual CPU mesh so distributed/sharding
paths are exercised without TPU hardware (SURVEY.md §4: the reference runs
its native-operator tests without a JVM; we run ours without a TPU)."""

import os

# Must be set before jax import. Force CPU: the suite validates semantics and
# the 8-device sharding paths; what differs on real hardware is covered by
# chip_smoke.py, run on the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import blaze_tpu  # noqa: E402,F401  (enables x64, places the compile cache)
from blaze_tpu.utils import native  # noqa: E402

native.ensure_built()  # host kernels exist before any test runs a query

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, devs
    return devs[:8]


@pytest.fixture(scope="module", autouse=True)
def _no_join_maps_left_behind():
    """`ops/joins/bhj.py` keeps built join maps per process under the plan's
    id, and several test files reuse `bench.py`'s ids over different data:
    whichever ran second on a worker probed the first one's map. Test-only;
    the engine's cache is as it was."""
    yield
    from blaze_tpu.ops.joins.bhj import clear_build_cache

    clear_build_cache()
