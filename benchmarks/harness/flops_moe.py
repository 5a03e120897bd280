"""Operations and bytes of a routed-experts configuration, from its shapes
and from how many experts a step touched (the program's counter).

Kept with the benchmark, beside ``flops.py`` (whose ``decode_step_min_bytes``
is dense: every matmul weight once). A decode step of a routed model has to
read only the experts its rows chose, so its least bytes depend on the
routing, and the counter says what that was.

``config`` is a configuration file (published keys); ``intermediate_size``
is the width of one expert.
"""

from __future__ import annotations


def head_dim(config: dict) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


def expert_params(config: dict) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def router_params(config: dict) -> int:
    return config["hidden_size"] * config["num_experts"]


def attention_params(config: dict) -> int:
    """One layer's q, k, v and o projections (the norms are vectors)."""
    d, hd = config["hidden_size"], head_dim(config)
    q = d * config["num_attention_heads"] * hd
    kv = d * config["num_key_value_heads"] * hd
    return 2 * q + 2 * kv


def experts_flops(config: dict, assignments: int) -> int:
    """Multiply-adds x 2 of ``assignments`` (token, expert) pairs through
    an expert's three matrices."""
    return 2 * assignments * expert_params(config)


def experts_step_min_bytes(config: dict, touched_per_layer: float,
                           bytes_per_param: int = 2) -> float:
    """What the expert layers of one decode step have to read: in every
    layer the router and the three matrices of each expert some live row
    chose (``touched_per_layer``, a mean over layers and steps).
    Bandwidth-bound: a touched expert serves one or two rows, 2-4 FLOPs a
    weight byte against the chip's 240."""
    per_layer = (touched_per_layer * expert_params(config)
                 + router_params(config))
    return config["num_hidden_layers"] * per_layer * bytes_per_param


def decode_step_min_bytes(config: dict, touched_per_layer: float,
                          live_tokens: int, bytes_per_param: int = 2) -> float:
    """A whole decode step: the touched experts and routers, every layer's
    attention projections, the output head (the embedding is a gather of a
    few rows), and the keys and values of the tokens in context."""
    layers = config["num_hidden_layers"]
    weights = (layers * attention_params(config)
               + config["hidden_size"] * config["vocab_size"])
    kv_per_token = (2 * config["num_key_value_heads"] * head_dim(config)
                    * layers * bytes_per_param)
    return (experts_step_min_bytes(config, touched_per_layer, bytes_per_param)
            + weights * bytes_per_param + live_tokens * kv_per_token)
