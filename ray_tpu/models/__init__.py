"""ray_tpu.models: TPU-first model families (GSPMD logical-axis sharding).

Llama (causal LM + LoRA + KV-cache decode), MoE transformer (routed
experts, dropless for serving), ViT (vision encoder). The reference
delegates model execution to torch/vLLM; this framework owns it.

This module is also the one place that chooses a family for the serving
stack (``llm/engine.py``, ``llm/serving.py``, ``llm/batch.py``), by the
type of the model config. What the engine asks of a family:

- ``build(config, mesh, decode)`` returns a flax module whose
  ``apply({"params": p[, "cache": c]}, tokens, adapters, adapter_slots)``
  gives ``(batch, seq, vocab)`` logits; with ``decode=True`` and
  ``mutable=["cache"]`` it writes the ``cache`` collection of
  ``models/llama.py``'s ``Attention``: per layer ``cached_key`` /
  ``cached_value`` of ``(batch, kv_heads, max_seq_len, head_dim)`` (the
  sequence axis at -2) and a per-row ``cache_index`` of ``(batch,)``
- ``init_params(config, rng, mesh)`` returns the boxed parameter tree
- the config carries ``max_seq_len``, ``n_heads``, ``n_kv_heads``,
  ``dtype`` and ``param_dtype``
- a family with routed experts has ``n_experts`` on its config and sows
  ``layer_<i>/moe/experts`` into the ``ROUTING`` collection when it is
  mutable
- a feature the family has no rules for (adapter bank, speculative draft,
  a ``tp``/``sp`` mesh) is listed in ``_NO_RULES`` with the reason, and
  ``LLMConfig`` refuses it at construction: no silent fallback

A family is added by a module with those two functions, a branch in
``_family`` and ``LLMConfig.build_model_config``, and its line here.
"""

from __future__ import annotations

from typing import Dict

# the collection a routed family's decode step sows each layer's (rows, k)
# expert choices into, for the engine's expert counters (llm/engine.py)
ROUTING = "moe_routing"

# family -> feature -> why LLMConfig refuses it
_NO_RULES: Dict[str, Dict[str, str]] = {
    "llama": {},
    "moe": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig and "
            "has no placement for routed expert weights"
        ),
        "draft_model": (
            "speculative verify has not been checked against routed "
            "experts (a draft's accepted run changes which rows share an "
            "expert), and the expert counters count plain decode steps"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for the (expert, ...) "
            "weights and the grouped expert kernel has no shard_map form "
            "yet (ROADMAP R1: ep rules)"
        ),
    },
}


def refusals(family: str) -> Dict[str, str]:
    """Serving features ``family`` has no rules for yet, with the reason."""
    if family not in _NO_RULES:
        raise ValueError(f"unknown model family {family!r}")
    return _NO_RULES[family]


def _family(model_config):
    from . import llama, moe

    if isinstance(model_config, moe.MoEConfig):
        return moe
    if isinstance(model_config, llama.LlamaConfig):
        return llama
    raise TypeError(
        f"no model family for a {type(model_config).__name__}"
    )


def build(model_config, mesh=None, decode: bool = False):
    """The family's flax module for ``model_config``."""
    return _family(model_config).build(model_config, mesh, decode)


def init_params(model_config, rng, mesh=None):
    return _family(model_config).init_params(model_config, rng, mesh)
