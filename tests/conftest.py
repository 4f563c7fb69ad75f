"""Test config: force JAX onto a virtual 8-device CPU platform.

Mirrors the reference's strategy of testing distributed semantics on
`local[*]` Spark (SURVEY.md §4): identical semantics, one process. Meshes
built in tests span 8 virtual CPU devices.
"""

import os
import re

# The tests run on the CPU backend whatever the machine holds: set before
# jax is imported (nothing preloads it), and again through jax.config below
# for a run in which some plugin imported jax first — the backend is not
# initialized until a test touches it, so the config still applies.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
_m = re.search(r"--xla_force_host_platform_device_count=(\d+)", _flags)
if _m is None:
    _flags += " --xla_force_host_platform_device_count=8"
elif int(_m.group(1)) < 8:
    _flags = _flags.replace(
        _m.group(0), "--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The program keeps its persistent compile cache at <checkout>/.jax_cache
# by default (serving/aot.py). The suite compiles thousands of tiny CPU
# programs, many across six workers at once: it runs with the cache off,
# and the tests of the cache's placement turn it on around themselves.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

from predictionio_tpu.data.storage import reset_storage, use_memory_storage  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running load/throughput tests excluded from tier-1 "
        "(run with `-m slow`)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / crash-recovery suite (tests marked ONLY "
        "chaos are the fast smoke subset and run in tier-1; the heavy "
        "legs carry chaos+slow and run with `-m chaos`)")


@pytest.fixture()
def memory_storage():
    """A fresh all-in-memory Storage singleton per test."""
    storage = use_memory_storage()
    yield storage
    reset_storage()
