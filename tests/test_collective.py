"""Collective library: GCS-KV backend across actors, XLA backend on the
device mesh (reference test model: util/collective tests)."""

import jax
import numpy as np
import pytest
from jax import shard_map

import ray_tpu
from ray_tpu.collective import ReduceOp
from ray_tpu.collective.xla_group import XlaGroup


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def test_gcs_backend_across_actors(cluster):
    @ray_tpu.remote
    class Member:
        def __init__(self, rank, world):
            import ray_tpu.collective as col

            self.col = col
            self.group = col.init_collective_group(
                world, rank, backend="gcs", group_name="t1"
            )
            self.rank = rank

        def do_allreduce(self):
            return self.group.allreduce(np.full((4,), self.rank + 1.0))

        def do_allgather(self):
            return self.group.allgather(np.array([self.rank]))

        def do_broadcast(self):
            return self.group.broadcast(np.array([42.0 + self.rank]), src_rank=1)

        def do_barrier(self):
            self.group.barrier()
            return True

    members = [Member.remote(r, 3) for r in range(3)]
    out = ray_tpu.get([m.do_allreduce.remote() for m in members], timeout=180)
    for arr in out:
        np.testing.assert_allclose(arr, np.full((4,), 6.0))
    gathered = ray_tpu.get([m.do_allgather.remote() for m in members], timeout=180)
    for g in gathered:
        assert [int(x[0]) for x in g] == [0, 1, 2]
    bc = ray_tpu.get([m.do_broadcast.remote() for m in members], timeout=180)
    assert all(float(b[0]) == 43.0 for b in bc)
    assert all(ray_tpu.get([m.do_barrier.remote() for m in members], timeout=180))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_xla_group_device_collectives():
    group = XlaGroup(1, 0, "xla-test", devices=jax.devices()[:4])
    x = np.arange(8, dtype=np.float32)  # 2 elements per device
    total = np.asarray(group.allreduce(x))
    # allreduce sums the per-device shards
    np.testing.assert_allclose(total, x.reshape(4, 2).sum(0))
    gathered = np.asarray(group.allgather(x))
    np.testing.assert_allclose(gathered, x)
    # single-process regime: input is the per-device contribution (replicated),
    # device i holds slice i of the sum; the global view concatenates shards
    rs = np.asarray(group.reducescatter(x))
    np.testing.assert_allclose(rs, 4 * x)
    group.barrier()


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_lax_helpers_in_shard_map():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("g",))

    def body(x):
        total = XlaGroup.lax_allreduce(x, "g")
        gathered = XlaGroup.lax_allgather(x, "g")
        return total, gathered

    f = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P("g"),
            out_specs=(P(), P()), check_vma=False,
        )
    )
    x = np.arange(4, dtype=np.float32)
    total, gathered = f(x)
    np.testing.assert_allclose(np.asarray(total), [6.0])
    np.testing.assert_allclose(np.asarray(gathered), x)
