"""LLM deployment configuration.

Role-equivalent of the reference's LLMConfig (llm/_internal/serve/configs/
server_models.py): model family + engine kwargs + per-replica resources.
``tensor_parallel_size`` maps to the mesh ``tp`` axis instead of vLLM's
NCCL groups (reference: vllm_models.py:215,219).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class AdapterConfig:
    """Multi-tenant LoRA serving (ray_tpu.lora): each replica keeps a
    paged AdapterStore of ``max_live`` HBM slots at rank ``slot_rank``;
    requests name an adapter via ``@serve.multiplexed`` model-id or an
    explicit ``adapter_id`` field, and a cold adapter refills from
    ``source`` (``"weights:<prefix>"`` pulls ``<prefix>/<adapter_id>``
    over the weight plane — the int8 chunk codec makes per-tenant
    publishes near-free)."""

    max_live: int = 8  # resident adapter slots per replica
    slot_rank: int = 8  # the bank-wide LoRA rank (fixed: slots are paged)
    alpha: float = 16.0  # lora_b is pre-scaled by alpha/rank at attach
    source: Optional[str] = None  # "weights:<prefix>" | None (prewarm-only)
    # acquire() retry budget when every slot is pinned before the replica
    # raises BackPressureError (routers retry elsewhere)
    acquire_timeout_s: float = 5.0

    def __post_init__(self):
        if self.max_live < 1:
            raise ValueError("AdapterConfig.max_live must be >= 1")
        if self.slot_rank < 1:
            raise ValueError("AdapterConfig.slot_rank must be >= 1")
        if self.source is not None and not (
            callable(self.source) or str(self.source).startswith("weights:")
        ):
            raise ValueError(
                'AdapterConfig.source must be "weights:<prefix>" or a '
                f"callable, got {self.source!r}"
            )


@dataclass
class LLMConfig:
    model_id: str = "llama-tiny"
    # model construction: either a models.llama config name or kwargs
    model_family: str = "llama"  # a name of ray_tpu.models.FAMILIES
    model_kwargs: Dict[str, Any] = field(default_factory=dict)
    max_seq_len: int = 512
    max_batch_size: int = 8
    # parallelism (reference: engine_kwargs tensor_parallel_size / pp)
    tensor_parallel_size: int = 1
    sequence_parallel_size: int = 1
    # replica mesh shape, e.g. {"tp": 4} or {"tp": 2, "sp": 2}: the
    # declarative form of the two sizes above (and the one the docs
    # lead with — LLMConfig(mesh={"tp": 4})). When set it WINS over
    # tensor_parallel_size/sequence_parallel_size; unknown axes raise
    # MeshValidationError at construction, divisibility against the
    # local device count / model head count is checked at deployment
    # (PartitionPlan.for_model) before any jit.
    mesh: Optional[Dict[str, int]] = None
    # serving
    num_replicas: int = 1
    # queue-depth replica autoscaling (BASELINE configs[4]: "Llama-2-7B
    # serving with TPU replica autoscaling"); dict mirroring
    # serve.AutoscalingConfig fields (min_replicas/max_replicas/
    # target_ongoing_requests/...). When set, num_replicas is ignored and
    # the serve controller scales TPU replicas with request pressure.
    autoscaling_config: Optional[Dict[str, Any]] = None
    # closed-loop SLO autoscaling; dict mirroring serve.AutoscalePolicy
    # fields (target_ttft_p99_ms/target_queue_per_replica/min_replicas/
    # max_replicas/...). Takes precedence over autoscaling_config.
    autoscale_policy: Optional[Dict[str, Any]] = None
    # what one replica leases. None (default) asks for the chips the
    # replica's mesh needs — tp x sp, 1 without a mesh — when this node has
    # chips, and for a CPU alone when it has none (tests, CPU clusters).
    # A replica that leases chips runs in the worker process that owns them.
    resources_per_replica: Optional[Dict[str, float]] = None
    # generation defaults
    max_new_tokens: int = 64
    temperature: float = 0.0  # 0 = greedy
    # sampling seed: None (default) = fresh per replica process, so
    # temperature>0 replicas don't emit identical streams; set an int for
    # reproducible sampling
    # (a replica deployed without weights also draws its random-init
    # parameters from this seed, 0 when None)
    seed: Optional[int] = None
    # paged KV cache (ray_tpu.kvcache): how many kv_block_size-token blocks
    # the replica's engine keeps in a pool under its slots, with prefix
    # reuse and memory-gated admission; None = no pool, a dense row a slot
    # (the engine is the same one either way)
    kv_cache_blocks: Optional[int] = None
    kv_block_size: int = 32
    # leading prompt tokens hashed for prefix-affinity replica routing
    # (serve handle pow2 bias); 0 disables
    prefix_affinity_tokens: int = 16
    # int8 chunk codec for weight-plane publishes feeding this deployment
    # (serving.publish_llm_weights): every broadcast-tree hop — and the
    # replica warm-up pull that gates RUNNING — carries ~2x (bf16) / ~4x
    # (f32) fewer bytes; replicas dequantize at assembly straight into
    # their sharded layout
    quantized: bool = False
    # disaggregated prefill/decode serving: roles={"prefill": N,
    # "decode": M} splits the deployment into N prefill replicas (run
    # admission prefill only, ship committed KV) and M decode replicas
    # (adopt shipped blocks, decode without re-running prefill) behind an
    # ingress that routes the handoff. Requires kv_cache_blocks. None
    # keeps the fused single-role deployment.
    roles: Optional[Dict[str, int]] = None
    # join the cluster-wide KV prefix tier (ray_tpu.kvtier): replicas
    # register computed prefixes and resolve warm ones local-hit →
    # peer-pull → recompute. Implied for role replicas (the handoff rides
    # the same machinery); set True to let a fused deployment share
    # prefixes across replicas and autoscale scale-ups. Requires
    # kv_cache_blocks.
    kv_tier: bool = False
    # chunk codec for KV shipments ("raw" | "int8"): int8 halves (bf16) /
    # quarters (f32) the prefill→decode and peer-pull wire bytes, paid
    # with a bounded per-block quantization error (same codec as the
    # quantized weight plane)
    kv_ship_codec: str = "raw"
    # chunked prefill: per-engine-step prefill token budget so a long
    # prompt admission interleaves with in-flight decodes instead of
    # stalling them; 0 = prefill runs to completion at admission.
    prefill_chunk_tokens: int = 0
    # multi-tenant LoRA plane (ray_tpu.lora): an AdapterConfig (or its
    # dict form) turns each replica into a multiplexed adapter server —
    # paged slots, batched-gather decode, weight-plane refill.
    adapters: Optional[AdapterConfig] = None

    def __post_init__(self):
        from ..models import refusals

        tp, sp = self.effective_parallelism()
        using = {
            "adapters": self.adapters is not None,
            "mesh": tp > 1 or sp > 1,
            "prefill_chunk": self.prefill_chunk_tokens > 0,
        }
        for feature, reason in refusals(self.model_family).items():
            if using[feature]:
                raise ValueError(
                    f"LLMConfig: model_family {self.model_family!r} cannot "
                    f"serve with {feature} yet: {reason}"
                )
        if not self.model_kwargs.get("dropless", True):
            # no knob, whatever the family: with a capacity, a row's answer
            # would depend on the rows that share its batch, and the decode
            # model will not build
            raise ValueError(
                "LLMConfig: model_kwargs['dropless']=False: serving has no "
                "capacity path for routed experts"
            )
        if self.mesh is not None:
            from ..exceptions import MeshValidationError

            unknown = set(self.mesh) - {"tp", "sp"}
            if unknown:
                raise MeshValidationError(
                    f"LLMConfig.mesh axes {sorted(unknown)} not supported "
                    "for serving replicas; use 'tp' (tensor parallel) "
                    "and/or 'sp' (sequence parallel)"
                )
            for axis, size in self.mesh.items():
                if not isinstance(size, int) or size < 1:
                    raise MeshValidationError(
                        f"LLMConfig.mesh[{axis!r}] must be a positive "
                        f"int, got {size!r}"
                    )
        if self.resources_per_replica is None:
            from .._internal.accelerators import TpuAcceleratorManager

            tp, sp = self.effective_parallelism()
            chips = tp * sp if TpuAcceleratorManager.detect_num_chips() else 0
            self.resources_per_replica = {"TPU": float(chips), "CPU": 1.0}
        if self.kv_ship_codec not in ("raw", "int8"):
            raise ValueError(
                f"LLMConfig.kv_ship_codec must be 'raw' or 'int8', got "
                f"{self.kv_ship_codec!r}"
            )
        if self.roles is not None:
            unknown = set(self.roles) - {"prefill", "decode"}
            if unknown:
                raise ValueError(
                    f"LLMConfig.roles keys {sorted(unknown)} not "
                    "supported; use 'prefill' and 'decode'"
                )
            for role_name in ("prefill", "decode"):
                count = self.roles.get(role_name)
                if not isinstance(count, int) or count < 1:
                    raise ValueError(
                        f"LLMConfig.roles[{role_name!r}] must be a "
                        f"positive int, got {count!r}"
                    )
        if (self.roles is not None or self.kv_tier) and not self.kv_cache_blocks:
            raise ValueError(
                "disaggregated roles / kv_tier ship KV blocks and need a "
                "block pool: set kv_cache_blocks"
            )
        if self.prefill_chunk_tokens < 0:
            raise ValueError("prefill_chunk_tokens must be >= 0")
        if isinstance(self.adapters, dict):
            self.adapters = AdapterConfig(**self.adapters)

    def effective_parallelism(self) -> tuple:
        """(tp, sp) with ``mesh`` winning over the scalar fields."""
        if self.mesh is not None:
            return (self.mesh.get("tp", 1), self.mesh.get("sp", 1))
        return (self.tensor_parallel_size, self.sequence_parallel_size)

    def build_model_config(self):
        from .. import models

        config_type = models.config_type(self.model_family)
        kwargs = dict(self.model_kwargs)
        kwargs.setdefault("max_seq_len", self.max_seq_len)
        if self.model_family == "moe":
            # serving never drops an assignment (__post_init__ refused
            # the other value)
            kwargs["dropless"] = True
        return config_type.tiny(**kwargs) if self.model_id.endswith(
            "tiny"
        ) else config_type(**kwargs)
