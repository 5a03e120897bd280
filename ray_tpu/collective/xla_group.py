"""XLA/ICI collective backend — the tensor fast path.

Role-equivalent of the reference's NCCLGroup
(util/collective/collective_group/nccl_collective_group.py:121), redesigned
for TPU: instead of NCCL communicators, ops lower to XLA collectives
(jax.lax.psum / all_gather / psum_scatter / ppermute) over ICI.

Two regimes:

1. **In-graph (preferred)**: training code runs under jit on a Mesh; the
   "collective" is just the lax op and XLA schedules it on ICI. This class's
   static helpers expose that surface for shard_map code.

2. **Out-of-graph**: `allreduce(array)` etc. called between jit programs,
   matching the reference's eager `col.allreduce(tensor, group)` API. Within
   one process the ops run as a jitted shard_map over this host's devices.
   Across hosts the group bootstraps the jax.distributed runtime — the
   coordinator address rendezvouses through the GCS KV, mirroring the NCCL
   unique-id flow (nccl_collective_group.py:29) — after which jax sees the
   global device set and the same jitted collectives span hosts over ICI/DCN.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.gcs import keys as gcs_keys
from .base import BaseGroup, ReduceOp, tensor_nbytes
from .._internal.quantization import (
    dequantize_jax,
    quantize_jax,
    quantized_wire_nbytes,
)

_LAX_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
    # PRODUCT deliberately absent: XLA has no pprod collective
}


def _rendezvous_coordinator(group_name: str, rank: int, world_size: int,
                            timeout: float = 60.0) -> Optional[str]:
    """Agree on a jax.distributed coordinator address through the GCS KV
    (reference: NCCL unique-id rendezvous through internal KV)."""
    from .. import _worker_api

    if not _worker_api.is_initialized():
        return None
    worker = _worker_api.get_core_worker()
    client = worker.client_pool.get(*worker.gcs_address)
    key = gcs_keys.XLA_COORD.key(group_name)
    if rank == 0:
        import socket

        host = socket.gethostbyname(socket.gethostname())
        # deterministic port per group in the dynamic range (stable_hash:
        # builtin hash() is per-process randomized, ranks would disagree)
        from .._internal.hashing import stable_hash

        port = 20000 + (stable_hash(group_name) % 20000)
        addr = f"{host}:{port}"
        _worker_api.run_on_worker_loop(client.call("kv_put", key, addr.encode(), True))
        return addr
    deadline = time.time() + timeout
    while time.time() < deadline:
        raw = _worker_api.run_on_worker_loop(client.call("kv_get", key))
        if raw:
            return raw.decode()
        time.sleep(0.05)
    raise TimeoutError(f"no coordinator for group {group_name}")


class XlaGroup(BaseGroup):
    """Out-of-graph collective group over this process's jax devices (and,
    multi-host, the global device set after jax.distributed bootstrap)."""

    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        *,
        bootstrap_distributed: bool = False,
        devices: Optional[List] = None,
        epoch: int = 0,
        quantized: bool = False,
        quant_block: int = 0,
    ):
        super().__init__(world_size, rank, group_name, epoch=epoch,
                         quantized=quantized, quant_block=quant_block)
        self._host = None
        if bootstrap_distributed and world_size > 1:
            coord = _rendezvous_coordinator(group_name, rank, world_size)
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=world_size,
                process_id=rank,
            )
        elif world_size > 1 and jax.process_count() < world_size:
            # without the distributed runtime each process would reduce over
            # its local devices only — numerically wrong results with no
            # error. Refuse instead.
            raise ValueError(
                f"XlaGroup world_size={world_size} but this jax runtime spans "
                f"{jax.process_count()} process(es); pass "
                f"bootstrap_distributed=True (or bootstrap jax.distributed "
                f"yourself) so collectives span all ranks"
            )
        self.devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(np.array(self.devices), ("g",))
        n = len(self.devices)

        spec = P("g")
        rep = P()

        @partial(jax.jit, static_argnums=(1,))
        def _reduce(x, op_name):
            fn = {
                "sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
            }[op_name]
            return shard_map(
                lambda s: fn(s, "g"),
                mesh=self.mesh, in_specs=spec, out_specs=rep, check_vma=False,
            )(x)

        self._reduce = _reduce

        @jax.jit
        def _allgather(x):
            return shard_map(
                lambda s: jax.lax.all_gather(s, "g", axis=0, tiled=True),
                mesh=self.mesh, in_specs=spec, out_specs=rep, check_vma=False,
            )(x)

        self._allgather = _allgather

        @jax.jit
        def _reducescatter(x):
            return shard_map(
                lambda s: jax.lax.psum_scatter(s, "g", scatter_dimension=0, tiled=True),
                mesh=self.mesh, in_specs=rep, out_specs=spec, check_vma=False,
            )(x)

        self._reducescatter = _reducescatter

        # -- quantized programs (EQuARX-style): quantize → exchange int8 +
        # scales → dequantize → reduce is ONE jitted computation per input
        # aval — the compressed payload is what crosses ICI, and nothing
        # round-trips through the host between the encode and the reduce.
        # The error-feedback residual rides as a device-array input/output
        # of the same program (f32, sharded like the operand), so carrying
        # it costs no extra transfer either.
        block = self.quant_block

        @jax.jit
        def _qallreduce(x, residual):
            def body(s, r):
                comp = s.astype(jnp.float32) + r
                q, scales = quantize_jax(comp, block)
                qg = jax.lax.all_gather(q, "g")
                sg = jax.lax.all_gather(scales, "g")
                total = dequantize_jax(
                    qg, sg, comp.shape, jnp.float32
                ).sum(axis=0)
                own = dequantize_jax(q, scales, comp.shape, jnp.float32)
                return total.astype(s.dtype), comp - own

            return shard_map(
                body, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=(rep, spec), check_vma=False,
            )(x, residual)

        self._qallreduce = _qallreduce

        @jax.jit
        def _qallgather(x):
            def body(s):
                q, scales = quantize_jax(s, block)
                qg = jax.lax.all_gather(q, "g")
                sg = jax.lax.all_gather(scales, "g")
                out = dequantize_jax(qg, sg, s.shape, s.dtype)
                # tiled concat along the shard axis, like the fp program
                return out.reshape((-1,) + s.shape[1:])

            return shard_map(
                body, mesh=self.mesh, in_specs=spec, out_specs=rep,
                check_vma=False,
            )(x)

        self._qallgather = _qallgather

        @jax.jit
        def _qreducescatter(x, residual):
            def body(xfull, r):
                comp = xfull.astype(jnp.float32) + r
                q, scales = quantize_jax(comp, block)
                qg = jax.lax.all_gather(q, "g")
                sg = jax.lax.all_gather(scales, "g")
                total = dequantize_jax(
                    qg, sg, comp.shape, jnp.float32
                ).sum(axis=0)
                own = dequantize_jax(q, scales, comp.shape, jnp.float32)
                idx = jax.lax.axis_index("g")
                shard_len = total.shape[0] // n
                shard = jax.lax.dynamic_slice_in_dim(
                    total, idx * shard_len, shard_len, 0
                )
                return shard.astype(xfull.dtype), comp - own

            return shard_map(
                body, mesh=self.mesh, in_specs=(rep, rep),
                out_specs=(spec, rep), check_vma=False,
            )(x, residual)

        self._qreducescatter = _qreducescatter

    def _device_shard(self, tensor):
        """Shard a host array over the group axis (leading dim)."""
        return jax.device_put(tensor, NamedSharding(self.mesh, P("g")))

    backend = "xla"

    def _timed(self, op_name: str, tensor, fn, wire_nbytes=None):
        """Run an eager collective under the bytes/latency instrumentation;
        block_until_ready so the recorded latency covers the ICI transfer,
        not just the async dispatch (the eager surface is synchronizing
        anyway — in-graph lax collectives stay untouched)."""
        start = time.perf_counter()
        out = jax.block_until_ready(fn())
        self._record_op(op_name, tensor_nbytes(tensor), start,
                        wire_nbytes=wire_nbytes)
        return out

    def _use_quantized(self, x, op: Optional[ReduceOp] = None) -> bool:
        """Quantized transport applies to float operands; reductions only
        for SUM (MIN/MAX order statistics have no meaningful additive
        error feedback, and their fp programs stay exact)."""
        from .._internal.quantization import is_quantizable

        return (
            self.quantized
            and is_quantizable(x)
            and (op is None or op is ReduceOp.SUM)
        )

    def _residual_for(self, op_name: str, x, replicated: bool = False):
        """The carried error-feedback residual for this (op, aval) —
        an f32 device array born zero, sharded like the operand so the
        jitted program consumes it without a relayout."""
        key = (op_name, tuple(x.shape), str(x.dtype))
        res = self._ef_residuals.get(key)
        if res is None or res.shape != x.shape:
            res = jax.device_put(
                jnp.zeros(x.shape, jnp.float32),
                NamedSharding(self.mesh, P() if replicated else P("g")),
            )
        return key, res

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        # each device's shard is summed: for the eager API the input is the
        # per-rank contribution replicated per device slot
        if op == ReduceOp.PRODUCT:
            raise NotImplementedError(
                "PRODUCT has no XLA collective; use the cpu backend"
            )
        x = self._device_shard(tensor)
        if self._use_quantized(x, op):
            key, res = self._residual_for("allreduce", x)

            def run():
                out, self._ef_residuals[key] = self._qallreduce(x, res)
                return out

            return self._timed(
                "allreduce", x, run,
                wire_nbytes=quantized_wire_nbytes(x.size, self.quant_block),
            )
        return self._timed("allreduce", x, lambda: self._reduce(x, op.value))

    def allreduce_async(self, tensor, op: ReduceOp = ReduceOp.SUM):
        """Dispatch-without-block: launch the jitted (possibly quantized)
        reduce program and hand back the not-yet-ready device array. jit
        dispatch is asynchronous, so no helper thread is needed — the
        program runs on the device stream while the caller keeps going;
        the handle's ``wait`` is block_until_ready. Metrics for the op are
        recorded at completion (on_ready), not dispatch."""
        from .scheduler import DeviceHandle

        if op == ReduceOp.PRODUCT:
            raise NotImplementedError(
                "PRODUCT has no XLA collective; use the cpu backend"
            )
        x = self._device_shard(tensor)
        nbytes = tensor_nbytes(x)
        if self._use_quantized(x, op):
            key, res = self._residual_for("allreduce", x)
            out, self._ef_residuals[key] = self._qallreduce(x, res)
            wire = quantized_wire_nbytes(x.size, self.quant_block)
        else:
            out = self._reduce(x, op.value)
            wire = None

        def on_ready(latency_s: float):
            from ..util import metrics

            metrics.record_collective(
                "allreduce", self.backend, self.group_name, nbytes,
                latency_s, wire_nbytes=wire,
            )

        return DeviceHandle(out, on_ready=on_ready)

    def allgather(self, tensor) -> Any:
        x = self._device_shard(tensor)
        if self._use_quantized(x):
            return self._timed(
                "allgather", x, lambda: self._qallgather(x),
                wire_nbytes=quantized_wire_nbytes(x.size, self.quant_block),
            )
        return self._timed("allgather", x, lambda: self._allgather(x))

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        if op != ReduceOp.SUM:
            raise NotImplementedError(
                "XLA psum_scatter only reduces with SUM; use the cpu backend"
            )
        x = jnp.asarray(tensor)
        if self._use_quantized(x, op) and x.shape[0] % len(self.devices) == 0:
            key, res = self._residual_for(
                "reducescatter", x, replicated=True
            )

            def run():
                out, self._ef_residuals[key] = self._qreducescatter(x, res)
                return out

            return self._timed(
                "reducescatter", x, run,
                wire_nbytes=quantized_wire_nbytes(x.size, self.quant_block),
            )
        return self._timed("reducescatter", x, lambda: self._reducescatter(x))

    def _host_group(self):
        # host-side control ops (broadcast/send/recv across processes)
        # delegate to the GCS-KV backend; device meshes have no eager
        # cross-process point-to-point path
        if self._host is None:
            from .cpu_group import GcsStoreGroup

            self._host = GcsStoreGroup(
                self.world_size, self.rank, f"{self.group_name}:host",
                epoch=self.epoch,
            )
        return self._host

    def broadcast(self, tensor, src_rank: int = 0):
        if self.world_size == 1:
            return jax.device_put(tensor, NamedSharding(self.mesh, P()))
        start = time.perf_counter()
        value = self._host_group().broadcast(tensor, src_rank)
        out = jax.device_put(value, NamedSharding(self.mesh, P()))
        self._record_op("broadcast", tensor_nbytes(out), start)
        return out

    def send(self, tensor, dst_rank: int):
        if self.world_size == 1:
            raise ValueError("send in a single-process group has no peer")
        return self._host_group().send(tensor, dst_rank)

    def recv(self, src_rank: int):
        if self.world_size == 1:
            raise ValueError("recv in a single-process group has no peer")
        return self._host_group().recv(src_rank)

    def barrier(self):
        start = time.perf_counter()
        x = jnp.zeros((len(self.devices),), jnp.int32)
        jax.block_until_ready(self._reduce(self._device_shard(x), "sum"))
        self._record_op("barrier", 0, start)

    def destroy(self):
        self._shutdown_async()
        if self._host is not None:
            self._host.destroy()
            self._host = None

    # -- in-graph surface (use inside shard_map/jit) ------------------------

    @staticmethod
    def lax_allreduce(x, axis_name: str, op: ReduceOp = ReduceOp.SUM):
        fn = _LAX_REDUCERS.get(op)
        if fn is None:
            raise NotImplementedError(f"{op} has no XLA collective")
        return fn(x, axis_name)

    @staticmethod
    def lax_allgather(x, axis_name: str, axis: int = 0):
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)

    @staticmethod
    def lax_reducescatter(x, axis_name: str, axis: int = 0):
        return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)

    @staticmethod
    def lax_ppermute(x, axis_name: str, perm):
        return jax.lax.ppermute(x, axis_name, perm)

    @staticmethod
    def lax_all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )
