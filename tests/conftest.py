"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): distributed logic is
tested without real accelerators — XLA's CPU backend with
--xla_force_host_platform_device_count=8 plays the role of the reference's
fake "custom device" plugin + multi-process harness.
"""
import os

# set before jax is imported: the suite needs the 8-device virtual CPU mesh
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    yield
