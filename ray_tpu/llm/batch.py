"""Offline batch inference: an LLM stage for ray_tpu.data pipelines.

Role-equivalent of the reference's vLLM batch stage
(llm/_internal/batch/stages/vllm_engine_stage.py — a map_batches UDF class
holding an engine): use with ``Dataset.map_batches(LLMPredictor, ...,
compute=ActorPoolStrategy(size=N))`` so each actor pins one engine (and its
TPU chips) and streams batches through it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .config import LLMConfig
from .engine import ContinuousBatchingEngine, GenerationRequest


class LLMPredictor:
    """map_batches UDF: {"token_ids": list-of-lists} -> adds "generated".

    Params resolve in priority order: ``params_blob`` (serialized pytree
    shipped in the UDF constructor args), then ``weights_name`` (pulled
    from the weight plane on first construction inside each map actor —
    the blob never rides the task spec), then random init.

    An optional per-row ``"adapter_id"`` column multiplexes LoRA tenants
    through one engine: rows sharing a batch may name different adapters
    (or None for the base model) and still execute as one mixed batch via
    the batched-gather decode path. Requires ``llm_config.adapters``.
    """

    def __init__(self, llm_config: Optional[LLMConfig] = None,
                 params_blob: Optional[bytes] = None,
                 weights_name: Optional[str] = None):
        import jax

        from ..parallel.sharding import unbox_params

        self._config = llm_config or LLMConfig()
        model_config = self._config.build_model_config()
        if params_blob is not None:
            from .._internal import serialization

            params = serialization.loads(params_blob)
        elif weights_name is not None:
            from .. import weights

            _, params = weights.fetch(weights_name, timeout=60.0)
        else:
            from .. import models

            params = unbox_params(
                models.init_params(model_config, jax.random.PRNGKey(0))
            )
        self._adapter_store = None
        if self._config.adapters is not None:
            from ..lora import AdapterStore

            ac = self._config.adapters
            self._adapter_store = AdapterStore(
                model_config,
                max_live=ac.max_live,
                rank=ac.slot_rank,
                alpha=ac.alpha,
                source=ac.source,
                param_dtype=model_config.param_dtype,
            )
        # the engine the serving replicas run, without a block pool: a
        # batch's rows decode together in max_batch_size slots
        self._engine = ContinuousBatchingEngine(
            model_config, params,
            num_slots=self._config.max_batch_size,
            adapter_store=self._adapter_store,
        )

    def close(self) -> None:
        """Stop the engine's stepping thread and wait for it."""
        self._engine.close()

    def __del__(self):
        # the map actor drops its predictor: the thread goes with it
        if hasattr(self, "_engine"):  # else the constructor raised
            self.close()

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        prompts = batch["token_ids"]
        adapter_ids = batch.get("adapter_id")
        if adapter_ids is not None and self._adapter_store is None:
            raise ValueError(
                "batch has an 'adapter_id' column but LLMConfig.adapters "
                "is not configured"
            )
        leases: Dict[str, Any] = {}
        try:
            requests = []
            for i, p in enumerate(prompts):
                aid = adapter_ids[i] if adapter_ids is not None else None
                if aid is not None:
                    aid = str(aid)
                slot = -1
                if aid:
                    lease = leases.get(aid)
                    if lease is None:
                        lease = self._adapter_store.acquire(aid)
                        if lease is None:
                            raise RuntimeError(
                                f"no free adapter slot for {aid!r}: batch "
                                "names more live adapters than "
                                "adapters.max_live"
                            )
                        leases[aid] = lease
                    slot = lease.slot
                requests.append(GenerationRequest(
                    token_ids=list(p),
                    max_new_tokens=self._config.max_new_tokens,
                    temperature=self._config.temperature,
                    adapter_id=aid or None,
                    adapter_slot=slot,
                ))
            results = self._engine.generate(requests)
        finally:
            for lease in leases.values():
                self._adapter_store.release(lease)
        out = dict(batch)
        out["generated"] = [r.token_ids for r in results]
        return out
