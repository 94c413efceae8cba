"""Test harness: force an 8-device virtual CPU platform BEFORE jax imports.

All distributed paths (sharded top-k merge, TP decode, DP encode) are tested
on this virtual mesh per SURVEY §4's lesson (3) — no TPU pod needed.
"""

import os
import sys

# Force, don't setdefault: the ambient env points JAX_PLATFORMS at the real
# TPU chip, and tests must never grab it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from docqa_tpu.runtime.mesh import host_cpu_mesh

    return host_cpu_mesh(8, data=2)


@pytest.fixture(scope="session")
def mesh_tp8():
    from docqa_tpu.runtime.mesh import host_cpu_mesh

    return host_cpu_mesh(8, data=1)
