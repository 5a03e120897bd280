"""Logical-axis sharding rules (GSPMD parameter partitioning).

The t5x/flax "logical axis" pattern: model code annotates parameters with
logical axis names ("embed", "mlp", "heads", ...); a rule table maps logical
names to mesh axes; pjit + XLA GSPMD insert the collectives. This replaces
the reference's delegation of TP/FSDP to torch/vLLM (SURVEY §2c).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rule table: logical axis -> mesh axis (or None = replicate).
# Weights shard "embed" over fsdp (ZeRO-3 style) and output/mlp/head dims over
# tp (megatron style); activations shard batch over the data axes and
# sequence over sp.
DEFAULT_RULES: List[Tuple[str, Any]] = [
    ("batch", ("dcn", "dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("layers", None),
    ("lora_rank", None),
]


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, Any]]] = None,
) -> P:
    table = dict(rules or DEFAULT_RULES)
    return P(*[table.get(name) if name else None for name in logical_axes])


def matrix_shards(mesh: Optional[Mesh], rules=None) -> int:
    """Over how many devices the rules cut a weight matrix ("embed" by "mlp",
    "heads" or "vocab"): what divides the parameters' bytes a device."""
    if mesh is None:
        return 1
    table = dict(rules or DEFAULT_RULES)
    axes = set()
    for name in ("embed", "mlp"):
        over = table.get(name) or ()
        axes.update((over,) if isinstance(over, str) else over)
    return math.prod(mesh.shape.get(a, 1) for a in axes)


def tree_shardings(
    mesh: Mesh,
    logical_tree: Any,
    rules: Optional[Sequence[Tuple[str, Any]]] = None,
):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


def constrain(x, mesh: Mesh, *logical_axes: Optional[str], rules=None):
    """with_sharding_constraint by logical axis names."""
    spec = logical_to_spec(logical_axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_shardings(mesh: Mesh, params: Any, rules=None):
    """Shardings for a parameter pytree carrying flax logical-axis metadata
    (nn.with_logical_partitioning) — falls back to replication for leaves
    without metadata."""
    import flax.linen as nn

    def leaf_sharding(leaf):
        if hasattr(leaf, "names"):  # nn.Partitioned / LogicallyPartitioned
            return NamedSharding(mesh, logical_to_spec(leaf.names, rules))
        return NamedSharding(mesh, P())

    # unbox flax Partitioned wrappers to their metadata
    return jax.tree.map(
        leaf_sharding,
        params,
        is_leaf=lambda x: hasattr(x, "names"),
    )


def unbox_params(params: Any):
    """Strip flax partitioning metadata boxes, returning raw arrays."""
    import flax.linen as nn

    return nn.meta.unbox(params)


def process_local_batch(mesh: Mesh, local, batch_axes=("dcn", "dp", "fsdp")):
    """Assemble a GLOBAL batch array from this process's local shard — the
    canonical SPMD data-feeding step under jax.distributed (each host loads
    its slice of the batch; the result is one global jax.Array sharded over
    the mesh's data axes). Single-process meshes take the same path, so
    example/training code is identical on a laptop and a pod.

    ``local`` is (per_process_batch, ...); the global batch is
    per_process_batch * process_count. Feeding a rank-local array straight
    into a jit over a multi-host mesh is an error (non-addressable
    shardings) — this is the supported route.
    """
    import numpy as np

    axes = tuple(a for a in batch_axes if a in mesh.shape)
    spec = P(axes, *([None] * (local.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    global_shape = (
        local.shape[0] * jax.process_count(), *local.shape[1:]
    )
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local), global_shape
    )
