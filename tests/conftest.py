"""Test configuration.

Tests run on the CPU platform: multi-chip behavior on a virtual 8-device
CPU mesh, Pallas kernels in interpret mode. ``JAX_PLATFORMS`` and
``XLA_FLAGS`` are set here, before any test imports jax, and the worker
subprocesses the runtime spawns inherit both. What the TPU's compiler says
about the kernels and step programs is tests/test_chip_compile.py.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running coverage excluded from the budgeted tier-1 lane "
        "(-m 'not slow'); run explicitly or without the marker filter",
    )


@pytest.fixture(scope="module")
def http_port():
    """A free port for this module's serve proxy. The driver runs the test
    files in six processes at once, and a request to the default 8000 is
    answered by whichever file's proxy bound it first: 404 for a route of
    another file's cluster. Should another process take the port before the
    proxy binds it, ``serve.start`` raises."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def ray_start_regular():
    """Single-node cluster, torn down after the test (reference:
    tests/conftest.py ray_start_regular)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, resources={"TPU": 4})
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    import ray_tpu

    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only_with_token():
    """Cluster with RPC auth on; clears the process-global token after."""
    import ray_tpu

    ray_tpu.init(num_cpus=2, _system_config={"cluster_auth_token": "tok-dbg"})
    yield ray_tpu
    ray_tpu.shutdown()
    from ray_tpu._internal.rpc import set_auth_token

    set_auth_token(None)


@pytest.fixture
def cluster():
    """Default 2-CPU local cluster; yields the ray_tpu module."""
    import ray_tpu

    ray_tpu.init(num_cpus=2)
    yield ray_tpu
    ray_tpu.shutdown()
