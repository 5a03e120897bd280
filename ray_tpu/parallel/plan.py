"""Partition-rule planner: compile-with-plan for the serving engine.

The t5x/EasyLM ``match_partition_rules`` idiom applied to this framework's
serving plane: a :class:`PartitionPlan` owns a mesh plus an ordered table of
``(path regex, PartitionSpec)`` rules, matches them against flax parameter
*path names* (``layer_0/attn/wq/base/kernel``), and hands the engine
everything it needs to compile sharded programs — parameter shardings,
decode-cache shardings (KV heads over ``tp``), and the paged block-pool
sharding.

This is deliberately name-based rather than metadata-based: the serving
path holds *unboxed* parameter pytrees (weight-plane subscriptions and
``params_blob`` deployments carry raw arrays, no flax logical-axis boxes),
so the train-path :func:`~ray_tpu.parallel.sharding.param_shardings` cannot
see their axes. Regex rules over tree paths work on any raw pytree and keep
one authoritative table per model family.

Sharding layout (megatron-style TP, the PAPERS.md Gemma-on-TPU serving
recipe):

- wq/wk/wv kernels ``(embed, heads*d)`` shard the output axis over ``tp``;
  wo ``(heads*d, embed)`` shards the input axis — one psum per attention.
- w_gate/w_up shard ``intermediate`` over ``tp``; w_down shards its input —
  one psum per MLP.
- ``embed (vocab, dim)`` and ``lm_head (dim, vocab)`` shard the vocab axis.
- norms, LoRA adapters, and scalars replicate.
- decode-cache KV leaves ``(b, heads, seq, d)`` shard heads; the per-row
  ``cache_index`` replicates. The paged block pools ``(capacity, heads,
  block, d)`` use the *same* spec — axis 1 is heads in both layouts, so
  commit/assemble stay single jitted programs over sharded buffers.

Everything runs under plain ``jax.jit`` with ``out_shardings`` (GSPMD
inserts the collectives); on a CPU box
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` exercises the same
programs tier-1 runs assert temperature-0 parity on.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..exceptions import MeshValidationError
from .mesh import make_mesh

# Ordered (path-regex, PartitionSpec) table for the Llama family (the MoE
# transformer reuses the same Attention module, so attention paths match;
# expert FFN weights fall through to the replicate catch-all). First match
# wins.
DEFAULT_LLM_RULES: List[Tuple[str, P]] = [
    (r"attn/(wq|wk|wv)/base/kernel$", P(None, "tp")),
    (r"attn/wo/base/kernel$", P("tp", None)),
    # LoRA adapter factors follow their base kernel: where the base shards
    # its output axis (wq/wk/wv), lora_b (rank, out) shards out and lora_a
    # replicates; where the base shards its input axis (wo), lora_a
    # (in, rank) shards in and lora_b replicates. Rank never shards.
    (r"attn/(wq|wk|wv)/lora_a$", P()),
    (r"attn/(wq|wk|wv)/lora_b$", P(None, "tp")),
    (r"attn/wo/lora_a$", P("tp", None)),
    (r"attn/wo/lora_b$", P()),
    (r"mlp/(w_gate|w_up)/kernel$", P(None, "tp")),
    (r"mlp/w_down/kernel$", P("tp", None)),
    (r"(^|/)embed$", P("tp", None)),
    (r"(^|/)lm_head$", P(None, "tp")),
    (r".*", P()),  # norms, router weights, scalars
]

# decode-cache / block-pool KV layout: heads at axis 1 in both
# (batch|capacity, heads, seq|block, head_dim)
KV_SPEC = P(None, "tp", None, None)


def _path_str(key_path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in key_path)


def match_partition_rules(
    rules: Sequence[Tuple[str, P]], params: Any
) -> Any:
    """Map a pytree of arrays to a pytree of PartitionSpecs by matching
    each leaf's '/'-joined tree path against ``rules`` (first match wins).
    Raises on an unmatched leaf — a silent replication default hides rule
    table typos, so custom tables must end with an explicit catch-all."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def pick(key_path, leaf):
        path = _path_str(key_path)
        for pat, spec in compiled:
            if pat.search(path):
                return spec
        raise MeshValidationError(
            f"no partition rule matches parameter {path!r}"
        )

    return jax.tree_util.tree_map_with_path(pick, params)


def validate_mesh_for_model(
    tensor_parallel_size: int,
    num_devices: int,
    n_heads: Optional[int] = None,
    n_kv_heads: Optional[int] = None,
    model_id: str = "?",
) -> None:
    """The admission gate for a sharded replica: every way ``tp`` can be
    wrong surfaces here as a typed :class:`MeshValidationError` instead of
    an opaque XLA shape error deep inside the first jit."""
    tp = int(tensor_parallel_size)
    if tp < 1:
        raise MeshValidationError(
            f"tensor_parallel_size must be >= 1, got {tp}"
        )
    if num_devices % tp != 0:
        raise MeshValidationError(
            f"tensor_parallel_size {tp} does not divide the local device "
            f"count {num_devices}; a replica's mesh must use whole devices"
        )
    for axis, n in (("n_heads", n_heads), ("n_kv_heads", n_kv_heads)):
        if n is not None and n % tp != 0:
            raise MeshValidationError(
                f"model {model_id!r}: {axis}={n} is not divisible by "
                f"tensor_parallel_size {tp}; attention heads (and the KV "
                f"block pools sharded along them) split evenly or not at all"
            )


class PartitionPlan:
    """One replica's sharding contract: mesh + rules + derived shardings.

    Built once per replica (``PartitionPlan.for_model``); the engine and
    the KV manager consume it instead of re-deriving specs locally, so the
    parameter layout, the decode-cache layout, and the block-pool layout
    can never drift apart.
    """

    def __init__(
        self,
        mesh: Mesh,
        rules: Optional[Sequence[Tuple[str, P]]] = None,
    ):
        self.mesh = mesh
        self.rules = list(rules or DEFAULT_LLM_RULES)

    # -- construction --------------------------------------------------------

    @classmethod
    def for_model(
        cls,
        model_config,
        tensor_parallel_size: int,
        sequence_parallel_size: int = 1,
        devices=None,
        rules: Optional[Sequence[Tuple[str, P]]] = None,
    ) -> "PartitionPlan":
        """Validate tp against the device count and the model's head
        counts, then build the replica mesh (tp on the fastest axis)."""
        num = len(list(devices) if devices is not None else jax.devices())
        validate_mesh_for_model(
            tensor_parallel_size,
            num,
            n_heads=getattr(model_config, "n_heads", None),
            n_kv_heads=getattr(model_config, "n_kv_heads", None),
            model_id=type(model_config).__name__,
        )
        mesh = make_mesh(
            tensor_parallel_size * max(1, sequence_parallel_size),
            tp=tensor_parallel_size,
            sp=sequence_parallel_size,
            fsdp=1,
            dp=1,
            devices=devices,
        )
        return cls(mesh, rules)

    # -- mesh facts ----------------------------------------------------------

    @property
    def tp(self) -> int:
        return int(self.mesh.shape.get("tp", 1))

    @property
    def num_devices(self) -> int:
        return int(self.mesh.size)

    def describe(self) -> str:
        """Compact mesh tag for spans/metrics/inventory: 'tp=2' (only
        non-trivial axes; 'tp=1' when fully trivial so the tag is never
        empty)."""
        parts = [
            f"{a}={s}" for a, s in self.mesh.shape.items() if s > 1
        ]
        return ",".join(parts) if parts else "tp=1"

    def mesh_shape(self) -> Dict[str, int]:
        return {a: int(s) for a, s in self.mesh.shape.items() if s > 1}

    # -- shardings -----------------------------------------------------------

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_shardings(self, params: Any) -> Any:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            match_partition_rules(self.rules, params),
            is_leaf=lambda x: isinstance(x, P),
        )

    def shard_params(self, params: Any) -> Any:
        """Place an (unboxed, host or device) parameter pytree into its
        sharded layout — each device materializes only its shard."""
        return jax.tree.map(
            jax.device_put, params, self.param_shardings(params)
        )

    def lora_bank_shardings(self, bank: Any) -> Any:
        """Shardings for an AdapterStore slot bank: each ``lora_a``/
        ``lora_b`` leaf is the per-adapter matrix with a leading
        ``num_slots`` axis prepended, so match the 2-D rule table against
        the tree paths and prepend a replicated slot axis to each spec."""
        specs = match_partition_rules(self.rules, bank)
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, P(None, *spec)),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def kv_sharding(self) -> NamedSharding:
        """KV leaves — decode-cache rows AND paged block pools (heads is
        axis 1 in both layouts)."""
        return NamedSharding(self.mesh, KV_SPEC)

    def cache_shardings(self, cache_shape: Any) -> Any:
        """Shardings for a decode-cache pytree (from jax.eval_shape or a
        live cache), by each leaf's kind (``ray_tpu.models``): sequence
        leaves shard heads, index leaves replicate, and a per-row state
        leaf has no rule (``models.refusals`` keeps such a family off a
        mesh)."""
        from ..models import INDEX, SEQUENCE, cache_kinds

        by_kind = {SEQUENCE: self.kv_sharding(), INDEX: self.replicated()}

        def sharding(_, kind):
            if kind not in by_kind:
                raise ValueError(
                    f"PartitionPlan: no partition rule for a {kind} cache leaf"
                )
            return by_kind[kind]

        return jax.tree.map(sharding, cache_shape, cache_kinds(cache_shape))
