"""ray_tpu.llm: LLM serving and batch inference.

Role-equivalent of the reference's ray.llm (python/ray/llm/): where the
reference wraps vLLM engines into Serve deployments
(llm/_internal/serve/.../vllm_models.py) and batch stages
(llm/_internal/batch/stages/vllm_engine_stage.py), the TPU-native engine is
a jitted JAX prefill/decode loop over this framework's own Llama family —
KV cache in a flax "cache" collection, bfloat16 on the MXU, TP/SP via the
mesh (GSPMD), replicas scheduled on TPU resources through serve.
"""

from .config import AdapterConfig, LLMConfig
from .engine import (
    ContinuousBatchingEngine,
    GenerationRequest,
    GenerationResult,
)
from .serving import build_llm_deployment, publish_llm_weights
from .batch import LLMPredictor

__all__ = [
    "AdapterConfig",
    "LLMConfig",
    "ContinuousBatchingEngine",
    "GenerationRequest",
    "GenerationResult",
    "build_llm_deployment",
    "publish_llm_weights",
    "LLMPredictor",
]
