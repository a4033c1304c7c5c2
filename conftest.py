"""Root conftest: pin the test suite to a virtual 8-device CPU mesh.

Sets ``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count=8``
before jax is imported, so every test runs in one process over an
in-process 8-device mesh — the analog of the reference's
``@distributed_test`` multiprocessing harness (tests/unit/common.py), which
forks N torch processes per test. The suite checks results, control flow
and counts; it measures no device (see chip_smoke.py for the chip).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
