"""Operations and bytes of a Cohere2-MoE-shaped configuration (a parallel
block, plain K/V heads in a ring on the window layers and full-length on
the others, a held share of routed experts as wide as the model, shared
experts averaged, a tied head), from its published keys:
``num_attention_heads`` / ``num_key_value_heads`` / ``head_dim``,
``sliding_window`` / ``layer_switch``, ``intermediate_size`` (an expert's
width), ``num_shared_experts``, ``num_experts`` (the experts *held*),
``published.num_experts`` (the router's width) and ``vocab_size`` (the
slice held). ``config`` is a configuration file.

Beside ``flops_gdla.py`` (a ring too, but of one latent row for all heads)
and ``flops_kda.py`` (a held share and GQA, but no ring): neither counts a
row that is a ring of K/V heads in some layers and grows in others.

What is counted is the work, not the implementation: a position's keys and
values read once a step whatever the chunking, a touched expert's three
matrices once whatever the blocking, each assignment's row in and out once.
The decode kernel at a group of 16 query heads does ``16 x 2 x 2 x 128``
operations on the ``512`` bytes of a K/V head's position: 16 FLOPs a byte
against the chip's ~240, so the bytes bound it; both shares are reported.
"""

from __future__ import annotations


def full_layers(config: dict) -> int:
    switch = config["layer_switch"]
    return sum(1 for i in range(config["num_hidden_layers"])
               if i % switch == switch - 1)


def window_layers(config: dict) -> int:
    return config["num_hidden_layers"] - full_layers(config)


def position_values(config: dict) -> int:
    """Values one cached position holds in one layer: K and V, every K/V
    head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"]


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """What a cached position costs: the full layers' rows only."""
    return full_layers(config) * position_values(config) * bytes_per_value


def window_bytes_per_row(config: dict, bytes_per_value: int = 2) -> int:
    """What a slot row carries whatever its length: the window layers'
    rings of ``sliding_window`` positions."""
    return (window_layers(config) * config["sliding_window"]
            * position_values(config) * bytes_per_value)


def attention_params(config: dict) -> int:
    """One layer's W_q, W_k, W_v and W_o."""
    d, width = config["hidden_size"], config["head_dim"]
    return d * width * 2 * (
        config["num_attention_heads"] + config["num_key_value_heads"])


def expert_params(config: dict) -> int:
    """One expert's gate, up and down matrices, routed or shared."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def shared_params(config: dict) -> int:
    return config["num_shared_experts"] * expert_params(config)


def router_params(config: dict) -> int:
    """The gate, over all the experts routed over."""
    routed = config.get("published", {}).get("num_experts", config["num_experts"])
    return config["hidden_size"] * routed


def dense_params(config: dict) -> int:
    """What a layer reads whatever is routed: q/k/v/o and the shared
    experts."""
    return attention_params(config) + shared_params(config)


def experts_kernel_min_bytes(config: dict, touched_per_layer: float,
                             held_assignments_per_layer: float,
                             bytes_per_value: int = 2) -> float:
    """What the grouped expert kernel of one decode step has to move, all
    layers: the three matrices of each *held* routed expert some live row
    chose (``touched_per_layer``: the program's counter) and each held
    assignment's row in and out."""
    per_layer = (touched_per_layer * expert_params(config)
                 + held_assignments_per_layer * 2 * config["hidden_size"])
    return config["num_hidden_layers"] * per_layer * bytes_per_value


def live_positions(config: dict, lengths) -> int:
    """Positions the decode kernel reads in one step over all layers, rows
    ``lengths`` long: a full layer a row's length, a window layer
    ``min(length, sliding_window)``."""
    window = config["sliding_window"]
    return (full_layers(config) * sum(lengths)
            + window_layers(config) * sum(min(n, window) for n in lengths))


def attention_step_min_bytes(config: dict, lengths, bytes_per_value: int = 2) -> float:
    return live_positions(config, lengths) * position_values(config) * bytes_per_value


def attention_step_flops(config: dict, lengths) -> float:
    """Multiply-adds x 2 over the live positions: a score and a value sum,
    ``head_dim`` wide each, a query head."""
    per_position = 2 * 2 * config["num_attention_heads"] * config["head_dim"]
    return live_positions(config, lengths) * per_position


def decode_step_min_bytes(config: dict, touched_per_layer: float, lengths,
                          bytes_per_param: int = 2) -> float:
    """A whole decode step: every layer's q/k/v/o, shared experts and
    router, the tied matrix's slice once as the head (the embedding is a
    gather of a few rows), the touched held experts, and the live rows."""
    weights = (config["num_hidden_layers"] * (
        dense_params(config) + router_params(config)
        + touched_per_layer * expert_params(config))
        + config["hidden_size"] * config["vocab_size"])
    return (weights * bytes_per_param
            + attention_step_min_bytes(config, lengths, bytes_per_param))
