"""Test configuration.

Tests run on the CPU platform: multi-chip behavior on a virtual 8-device
CPU mesh, Pallas kernels in interpret mode. ``JAX_PLATFORMS`` and
``XLA_FLAGS`` are set here, before any test imports jax, and the worker
subprocesses the runtime spawns inherit both. What the TPU's compiler says
about the kernels and step programs is tests/test_chip_compile.py.
"""

import functools
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


@functools.lru_cache(maxsize=None)
def _reference_program(cfg):
    """``cfg``'s whole forward pass, jitted once: (params, tokens padded to
    ``max_seq_len``, a position) -> that position's logits."""
    import jax

    from ray_tpu import models

    model = models.build(cfg)
    return jax.jit(
        lambda p, tokens, last: model.apply({"params": p}, tokens)[0, last]
    )


def greedy_reference(cfg, params, prompt, n_new):
    """The test reference for an engine's greedy tokens: whole forward
    passes of the family's training-form model (``models.build(cfg)``), no
    cache and none of the engine's code. One program a config: the tokens
    are padded to ``max_seq_len`` and the logits read at the last real
    position, which a causal model computes from the real tokens alone."""
    import numpy as np

    forward = _reference_program(cfg)
    toks = list(prompt)
    for _ in range(n_new):
        padded = np.zeros((1, cfg.max_seq_len), np.int32)
        padded[0, :len(toks)] = toks
        logits = forward(params, padded, np.int32(len(toks) - 1))
        toks.append(int(np.argmax(np.asarray(logits, np.float32))))
    return toks[len(prompt):]


def wait_for(predicate, timeout_s=60.0):
    """Poll until ``predicate()`` holds; fail the test if it never does.
    For what another process or the owner's loop does in its own time: a
    test asserts the state it reaches, never how long it took."""
    import time

    deadline = time.time() + timeout_s
    while not predicate():
        assert time.time() < deadline, "condition not reached"
        time.sleep(0.01)


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
