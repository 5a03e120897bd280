"""Operations and bytes of a DeepSeek-V3-shaped configuration (latent
attention, a leading dense layer, routed experts beside shared ones), from
its published keys and from how many experts a step touched.

Beside ``flops_moe.py``, which reads OLMoE's keys (``num_experts``,
``intermediate_size`` as an expert's width, K and V a head, every layer
routed): none of that holds here. ``config`` is a configuration file.
A decode step is bandwidth-bound throughout: a touched expert serves two or
three rows, and attention over the latent row does ``heads x 2 x (width +
rank)`` operations on ``width x 2`` bytes a position (~30 FLOPs a byte at
16 heads on 576, against the chip's 240).
"""

from __future__ import annotations


def latent_width(config: dict) -> int:
    """Values one cached position holds in one layer: ``[c | k_rope]``."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    return config["num_hidden_layers"] * latent_width(config) * bytes_per_value


def routed_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def attention_params(config: dict) -> int:
    """One layer's W_q, W_kva, W_kvb and W_o (the norms are vectors)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, v = config["kv_lora_rank"], config["v_head_dim"]
    return d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + v) + h * v * d


def expert_params(config: dict) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: dict) -> int:
    """A routed layer's shared experts, which every token passes."""
    return config["n_shared_experts"] * expert_params(config)


def dense_params(config: dict) -> int:
    """A dense layer's SwiGLU."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def router_params(config: dict) -> int:
    """The gate and its selection bias."""
    return (config["hidden_size"] + 1) * config["n_routed_experts"]


def experts_step_min_bytes(config: dict, touched_per_layer: float,
                           bytes_per_param: int = 2) -> float:
    """What the expert part of one decode step has to read: in every routed
    layer the router, the shared experts and the three matrices of each
    routed expert some live row chose (``touched_per_layer``, a mean over
    routed layers and steps)."""
    per_layer = (touched_per_layer * expert_params(config)
                 + shared_params(config) + router_params(config))
    return routed_layers(config) * per_layer * bytes_per_param


def attention_step_min_bytes(config: dict, live_tokens: int,
                             bytes_per_value: int = 2) -> float:
    """What the latent kernel has to read in one step: every live position's
    row once, in every layer (the queries and the output are a few KB)."""
    return live_tokens * kv_bytes_per_token(config, bytes_per_value)


def attention_step_flops(config: dict, live_tokens: int) -> float:
    """Multiply-adds x 2 of the absorbed form over the live positions: a
    score over the whole row and a value sum over its latent part, a head."""
    per_position = 2 * config["num_attention_heads"] * (
        latent_width(config) + config["kv_lora_rank"])
    return config["num_hidden_layers"] * live_tokens * per_position


def decode_step_min_bytes(config: dict, touched_per_layer: float,
                          live_tokens: int, bytes_per_param: int = 2) -> float:
    """A whole decode step: the expert part, every layer's attention
    projections, the dense layers, the output head (the embedding is a
    gather of a few rows), and the latent rows of the tokens in context."""
    weights = (config["num_hidden_layers"] * attention_params(config)
               + config["first_k_dense_replace"] * dense_params(config)
               + config["hidden_size"] * config["vocab_size"])
    return (experts_step_min_bytes(config, touched_per_layer, bytes_per_param)
            + weights * bytes_per_param
            + attention_step_min_bytes(config, live_tokens, bytes_per_param))
