"""Test fixtures.

Per the project environment contract, sharding tests run on a virtual 8-device CPU
mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8) — the analog of the
reference's in-process multi-raylet Cluster harness (python/ray/cluster_utils.py:141)
for simulating multi-node without hardware.
"""

import os

# Must be set before jax backend initialization.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Pin the config too, so tests never touch a real chip (one process may hold
# it at a time) whatever selected the platform before this file ran.
import jax

jax.config.update("jax_platforms", "cpu")

import pytest

# Smoke tier: `pytest -m fast` runs these modules (<2 min together) — the
# analog of the reference's small-size test tags (BUILD `size = "small"`).
# Keep this list to modules with no heavy jax compiles or process gangs.
_FAST_MODULES = {
    "test_core_tasks",
    "test_core_actors",
    "test_core_objects",
    "test_core_scheduling",
    "test_dag",
    "test_pubsub",
    "test_misc_parity",
    "test_round4_fixes",
    "test_rpdb",
    "test_util",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _FAST_MODULES:
            item.add_marker(pytest.mark.fast)


@pytest.fixture
def ray_start_regular():
    """Analog of the reference's ray_start_regular fixture (tests/conftest.py:616)."""
    import ray_tpu

    ctx = ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-(logical-)node session (reference: ray_start_cluster conftest.py:699)."""
    import ray_tpu

    ctx = ray_tpu.init(num_cpus=4, num_nodes=4, ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def counter_file(tmp_path):
    """Cross-process invocation counter (tasks run in worker processes by
    default, so closure-dict counters don't propagate back to the driver).
    Call it inside a task to bump; `.count()` reads from the driver."""
    path = str(tmp_path / "invocations")

    def bump():
        with open(path, "a") as f:
            f.write("x")
        with open(path) as f:
            return len(f.read())

    def count():
        try:
            with open(path) as f:
                return len(f.read())
        except FileNotFoundError:
            return 0

    bump.count = count
    return bump


@pytest.fixture
def cpu_mesh8():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 host devices"
    yield devices[:8]
