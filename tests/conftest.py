"""Test config: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware isn't available in CI; sharding correctness is
exercised on XLA's host platform with 8 virtual devices (same program, same
collectives). Must run before jax is imported anywhere.
"""

import os

# Tests always run on the CPU backend with 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture()
def tmp_store(tmp_path):
    from horaedb_tpu.utils.object_store import LocalDiskStore

    return LocalDiskStore(str(tmp_path / "store"))
