"""Operations and bytes of a SmallThinker-shaped configuration (a router
ahead of the attention, K/V heads in a ring on the window layers and
full-length on the others, ReGLU experts every layer, all of them held, an
untied head), from its published keys: ``num_attention_heads`` /
``num_key_value_heads`` / ``head_dim``, ``sliding_window_size`` /
``sliding_window_layout`` (1 a window layer), ``moe_ffn_hidden_size`` (an
expert's width), ``moe_num_primary_experts`` (the experts *held*),
``published.moe_num_primary_experts`` (the router's width, where a share is
held) and ``vocab_size``. ``config`` is a configuration file.

Beside ``flops_c2moe.py`` (the same rows, but a parallel block with shared
experts, a tied sliced vocabulary and ``layer_switch`` for a layout):
neither its dense part nor its order of layers is this model's.

What is counted is the work, not the implementation: a position's keys and
values read once a step whatever the chunking, a touched expert's three
matrices **once** whatever the blocking or the row tiles (a kernel that
visits an expert once a tile reads it more often: its share of this falls),
each assignment's row in and out once. The decode kernel at a group of 7
query heads does ``7 x 2 x 2 x 128`` operations on the ``512`` bytes of a
K/V head's position: 7 FLOPs a byte against the chip's ~240, so the bytes
bound it.
"""

from __future__ import annotations


def _layout(config: dict) -> list:
    return config["sliding_window_layout"][:config["num_hidden_layers"]]


def window_layers(config: dict) -> int:
    return sum(1 for kind in _layout(config) if kind)


def full_layers(config: dict) -> int:
    return config["num_hidden_layers"] - window_layers(config)


def position_values(config: dict) -> int:
    """Values one cached position holds in one layer: K and V, every K/V
    head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"]


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """What a cached position costs: the full layers' rows only."""
    return full_layers(config) * position_values(config) * bytes_per_value


def window_bytes_per_row(config: dict, bytes_per_value: int = 2) -> int:
    """What a slot row carries whatever its length: the window layers'
    rings of ``sliding_window_size`` positions."""
    return (window_layers(config) * config["sliding_window_size"]
            * position_values(config) * bytes_per_value)


def attention_params(config: dict) -> int:
    """One layer's W_q, W_k, W_v and W_o."""
    d, width = config["hidden_size"], config["head_dim"]
    return d * width * 2 * (
        config["num_attention_heads"] + config["num_key_value_heads"])


def expert_params(config: dict) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def router_params(config: dict) -> int:
    """The gate, over all the experts routed over."""
    routed = config.get("published", {}).get(
        "moe_num_primary_experts", config["moe_num_primary_experts"])
    return config["hidden_size"] * routed


def experts_kernel_min_bytes(config: dict, touched_per_layer: float,
                             assignments_per_layer: float,
                             bytes_per_value: int = 2) -> float:
    """What the grouped expert kernel of one decode step has to move, all
    layers: the three matrices of each held expert some live row chose,
    once (``touched_per_layer``: the program's counter), and each held
    assignment's row in and out."""
    per_layer = (touched_per_layer * expert_params(config)
                 + assignments_per_layer * 2 * config["hidden_size"])
    return config["num_hidden_layers"] * per_layer * bytes_per_value


def live_positions(config: dict, lengths) -> int:
    """Positions the decode kernel reads in one step over all layers, rows
    ``lengths`` long: a full layer a row's length, a window layer
    ``min(length, sliding_window_size)``."""
    window = config["sliding_window_size"]
    return (full_layers(config) * sum(lengths)
            + window_layers(config) * sum(min(n, window) for n in lengths))


def attention_step_min_bytes(config: dict, lengths, bytes_per_value: int = 2) -> float:
    return live_positions(config, lengths) * position_values(config) * bytes_per_value


def attention_step_flops(config: dict, lengths) -> float:
    """Multiply-adds x 2 over the live positions: a score and a value sum,
    ``head_dim`` wide each, a query head."""
    per_position = 2 * 2 * config["num_attention_heads"] * config["head_dim"]
    return live_positions(config, lengths) * per_position


def decode_step_min_bytes(config: dict, touched_per_layer: float,
                          assignments_per_layer: float, lengths,
                          bytes_per_param: int = 2) -> float:
    """A whole decode step: every layer's q/k/v/o and router, the head once
    (the embedding is a gather of a few rows), the touched experts and
    their assignments' rows in and out, and the live rows' keys and
    values."""
    dense = (config["num_hidden_layers"] * (
        attention_params(config) + router_params(config))
        + config["hidden_size"] * config["vocab_size"])
    return (dense * bytes_per_param
            + experts_kernel_min_bytes(
                config, touched_per_layer, assignments_per_layer, bytes_per_param)
            + attention_step_min_bytes(config, lengths, bytes_per_param))
