"""Operations and bytes of a Solar-Open2-shaped configuration (KDA mixers
three layers in four, a gated NoPE GQA layer the fourth, every layer's
feed-forward a held share of routed experts beside a shared one), from its
published keys: ``linear_attn_config``, ``gqa_layers``, the GQA keys,
``moe_intermediate_size``, ``n_routed_experts`` (the experts *held*),
``published.n_routed_experts`` (the router's width) and ``vocab_size`` (the
slice held). ``config`` is a configuration file.

Beside ``flops_ssm.py`` (a Mamba-2 state of heads x head x state beside
attention in every block, a dense SwiGLU) and ``flops_mla.py`` (latent
attention, all experts held): neither counts a delta-rule state, layers
that differ by index, or an expert layer that holds a share.

A decode step is bandwidth-bound throughout. The delta rule does ``9``
operations on the ``8`` bytes it moves a state element (decay, the two
readouts' multiply-adds, the rank-one update's multiply-add, read and
write): about one FLOP a byte against the chip's 240.
"""

from __future__ import annotations


def kda_layers(config: dict) -> int:
    depth = config["num_hidden_layers"]
    return depth - gqa_layers(config)


def gqa_layers(config: dict) -> int:
    depth = config["num_hidden_layers"]
    return sum(1 for i in config["gqa_layers"] if i < depth)


def kda_width(config: dict) -> int:
    """Heads x head size: what q, k, v, the decay and the gate each span."""
    linear = config["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def state_elements(config: dict) -> int:
    """Values of one KDA layer's state a row: heads x d_k x d_v."""
    linear = config["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"] ** 2


def tail_elements(config: dict) -> int:
    """Values of one KDA layer's convolution tail a row: the last
    ``short_conv_kernel_size - 1`` inputs of q, k and v a channel."""
    linear = config["linear_attn_config"]
    return (linear["short_conv_kernel_size"] - 1) * 3 * kda_width(config)


def state_bytes_per_row(config: dict, state_bytes: int = 4,
                        tail_bytes: int = 2) -> int:
    """What a slot row carries whatever its length, all KDA layers: the
    float32 state and the convolution tail."""
    return kda_layers(config) * (
        state_elements(config) * state_bytes
        + tail_elements(config) * tail_bytes)


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """K and V a position, all GQA layers."""
    return (gqa_layers(config) * 2 * config["num_key_value_heads"]
            * config["head_dim"] * bytes_per_value)


def kda_params(config: dict) -> int:
    """One KDA mixer: W_qkv and W_o, the two low-rank gates (rank = head
    size; g's bias), W_b, the convolution's taps, A_log, dt_bias, the
    norm."""
    d, width = config["hidden_size"], kda_width(config)
    linear = config["linear_attn_config"]
    rank, heads = linear["head_dim"], linear["num_heads"]
    return (d * 3 * width + width * d + 2 * (d * rank + rank * width) + width
            + d * heads + linear["short_conv_kernel_size"] * 3 * width
            + heads + width + linear["head_dim"])


def gqa_params(config: dict) -> int:
    """One GQA layer's W_q, W_k, W_v, W_o and its gate."""
    d, width = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return 3 * d * h * width + 2 * d * hk * width


def expert_params(config: dict) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config: dict) -> int:
    """The gate and its selection bias, over all the experts routed over."""
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    return (config["hidden_size"] + 1) * routed


def state_step_bytes(config: dict, rows: float, state_bytes: int = 4,
                     tail_bytes: int = 2) -> float:
    """What the recurrence of one decode step has to move: every stepped
    row's state and convolution tail in and out **once**, in every KDA
    layer (its other operands are a few KB a row). A form that reads the
    state twice moves more and reads a lower share."""
    return 2 * rows * state_bytes_per_row(config, state_bytes, tail_bytes)


def state_step_flops(config: dict, rows: float) -> float:
    return 9 * rows * kda_layers(config) * state_elements(config)


def experts_step_min_bytes(config: dict, touched_per_layer: float,
                           bytes_per_param: int = 2) -> float:
    """What the expert part of one decode step has to read: in every layer
    the router, the shared expert and the three matrices of each *held*
    routed expert some live row chose (``touched_per_layer``: the
    program's counter, over the experts held)."""
    per_layer = (touched_per_layer * expert_params(config)
                 + config["n_shared_experts"] * expert_params(config)
                 + router_params(config))
    return config["num_hidden_layers"] * per_layer * bytes_per_param


def decode_step_min_bytes(config: dict, rows: float, live_tokens: int,
                          touched_per_layer: float,
                          bytes_per_param: int = 2) -> float:
    """A whole decode step of ``rows`` live rows at ``live_tokens``
    positions in context over all of them: every mixer's weights and the
    head's slice once (the embedding is a gather of a few rows), the live
    rows' state and tails in and out, the live keys and values, and the
    expert part."""
    weights = (kda_layers(config) * kda_params(config)
               + gqa_layers(config) * gqa_params(config)
               + 2 * config["num_hidden_layers"] * config["hidden_size"]
               + config["hidden_size"] * config["vocab_size"])
    return (weights * bytes_per_param + state_step_bytes(config, rows)
            + live_tokens * kv_bytes_per_token(config, bytes_per_param)
            + experts_step_min_bytes(config, touched_per_layer, bytes_per_param))
