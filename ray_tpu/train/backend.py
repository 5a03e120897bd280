"""Framework backends: per-worker distributed setup.

Role-equivalent of the reference's backend configs
(train/v2/jax/config.py:21,73 — JaxConfig/_JaxBackend setting
JAX_PLATFORMS=tpu and running jax.distributed.initialize(master, n, rank) on
every ranked worker; train/torch/config.py — process-group bootstrap).

TPU-first: the JAX backend is the primary one. Rank 0 advertises a
coordinator address; every worker initializes the JAX distributed runtime so
the whole slice forms one multi-controller SPMD program and in-jit
collectives ride ICI.
"""

from __future__ import annotations

import logging
from typing import Optional

logger = logging.getLogger(__name__)


class BackendConfig:
    """Base: no distributed setup."""

    def backend(self) -> "Backend":
        return Backend()


class Backend:
    def on_start(self, worker_group) -> None:
        pass

    def on_shutdown(self, worker_group) -> None:
        pass


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _host_ip() -> str:
    import socket

    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"


# -- JAX ---------------------------------------------------------------------


class JaxConfig(BackendConfig):
    """JAX distributed runtime bootstrap.

    ``distributed=None`` (default) auto-enables jax.distributed for
    multi-worker TPU groups and disables it for single-worker or CPU test
    groups (where each worker process is an independent single-device JAX;
    cross-worker sync then goes through the framework's GCS collective
    group).
    """

    def __init__(self, use_tpu: bool = False, distributed: Optional[bool] = None):
        self.use_tpu = use_tpu
        self.distributed = distributed

    def backend(self) -> "Backend":
        return _JaxBackend(self)


def _jax_worker_setup(
    coordinator: Optional[str],
    num_processes: int,
    process_id: int,
):
    # which platform this worker's JAX may initialise was decided by the
    # worker pool before the process started (chip grant, or the CPU)
    from .. import get_tpu_ids
    from .._internal.platform import backend_initialized

    if coordinator is not None:
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    if get_tpu_ids() and not backend_initialized():
        # worker.startup: a worker leased chips for this loop attaches them
        # here, in a phase of its own (else: wherever the loop first touches
        # a device), and never before the distributed runtime is up. A CPU
        # worker's loop may never import jax, and is not made to
        import jax

        from ..util import tracing

        with tracing.startup_phase("backend") as phase:
            phase.count(devices=len(jax.local_devices()))
    if coordinator is not None:
        logger.info(
            "jax.distributed up: rank %d/%d coordinator %s devices=%d",
            process_id,
            num_processes,
            coordinator,
            jax.device_count(),
        )
    return True


def _jax_shutdown():
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    return True


class _JaxBackend(Backend):
    def __init__(self, config: JaxConfig):
        self._config = config
        self._initialized_distributed = False

    def on_start(self, worker_group):
        n = len(worker_group.workers)
        use_dist = self._config.distributed
        if use_dist is None:
            use_dist = self._config.use_tpu and n > 1
        coordinator = None
        if use_dist:
            # rank 0 advertises host:free-port (reference: config.py:41-68
            # master-address broadcast via worker 0)
            coordinator = worker_group.execute_single(
                0, lambda: f"{_host_ip()}:{_free_port()}"
            )
            self._initialized_distributed = True
        import functools

        refs = []
        for w in worker_group.workers:
            refs.append(
                w.actor.execute.remote(
                    _jax_worker_setup,
                    coordinator,
                    n,
                    w.world_rank,
                )
            )
        from .. import api as ray_api

        ray_api.get(refs)

    def on_shutdown(self, worker_group):
        if self._initialized_distributed:
            try:
                worker_group.execute(_jax_shutdown)
            except Exception:
                pass


# -- Torch -------------------------------------------------------------------


class TorchConfig(BackendConfig):
    """torch.distributed process-group bootstrap over TCP/gloo (CPU) for
    parity with the reference's TorchTrainer (train/torch/config.py)."""

    def __init__(self, backend: str = "gloo", timeout_s: int = 1800):
        self.backend_name = backend
        self.timeout_s = timeout_s

    def backend(self) -> "Backend":
        return _TorchBackend(self)


def _torch_worker_setup(master_addr, master_port, world_size, rank, backend, timeout_s):
    import datetime
    import os

    import torch.distributed as dist

    os.environ["MASTER_ADDR"] = str(master_addr)
    os.environ["MASTER_PORT"] = str(master_port)
    if not dist.is_initialized():
        dist.init_process_group(
            backend=backend,
            init_method=f"tcp://{master_addr}:{master_port}",
            world_size=world_size,
            rank=rank,
            timeout=datetime.timedelta(seconds=timeout_s),
        )
    return True


def _torch_shutdown():
    import torch.distributed as dist

    if dist.is_initialized():
        dist.destroy_process_group()
    return True


class _TorchBackend(Backend):
    def __init__(self, config: TorchConfig):
        self._config = config

    def on_start(self, worker_group):
        addr_port = worker_group.execute_single(
            0, lambda: (_host_ip(), _free_port())
        )
        n = len(worker_group.workers)
        from .. import api as ray_api

        refs = [
            w.actor.execute.remote(
                _torch_worker_setup,
                addr_port[0],
                addr_port[1],
                n,
                w.world_rank,
                self._config.backend_name,
                self._config.timeout_s,
            )
            for w in worker_group.workers
        ]
        ray_api.get(refs)

    def on_shutdown(self, worker_group):
        try:
            worker_group.execute(_torch_shutdown)
        except Exception:
            pass


# -- TensorFlow --------------------------------------------------------------


class TensorflowConfig(BackendConfig):
    """TF_CONFIG cluster bootstrap (reference: train/tensorflow/config.py —
    each ranked worker gets the full worker address list + its own index so
    tf.distribute.MultiWorkerMirroredStrategy forms the collective ring)."""

    def backend(self) -> "Backend":
        return _TensorflowBackend()


def _tf_advertise():
    return f"{_host_ip()}:{_free_port()}"


def _tf_worker_setup(cluster, rank):
    import json
    import os

    os.environ["TF_CONFIG"] = json.dumps(
        {
            "cluster": {"worker": list(cluster)},
            "task": {"type": "worker", "index": rank},
        }
    )
    return True


class _TensorflowBackend(Backend):
    def on_start(self, worker_group):
        from .. import api as ray_api

        # every worker advertises its own host:port — multi-host correct,
        # unlike deriving all addresses on rank 0; gathered concurrently
        # (workers are rank-ordered, so the list index IS the task index)
        cluster = ray_api.get(
            [
                w.actor.execute.remote(_tf_advertise)
                for w in worker_group.workers
            ]
        )
        ray_api.get(
            [
                w.actor.execute.remote(_tf_worker_setup, cluster, w.world_rank)
                for w in worker_group.workers
            ]
        )
