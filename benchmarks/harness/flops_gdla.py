"""Operations and bytes of a Motif-shaped configuration (grouped
differential latent attention on window and full layers, mHC maps around
every sub-layer, PolyNorm feed-forwards, leading dense layers and then a
held share of routed experts beside a shared one), from its published
keys: the attention's ranks and head sizes, ``sliding_window`` /
``sliding_window_period``, ``mhc_expansion_rate``, ``n_dense_first_layers``,
``num_experts`` (the experts *held*), ``published.num_experts`` (the
router's width) and ``vocab_size`` (the slice held). ``config`` is a
configuration file.

Beside ``flops_mla.py`` (every layer a full-length latent row, every expert
held, 16 heads) and ``flops_kda.py`` (a held share, but no latent row):
neither counts a row that is a ring in some layers and grows in others, a
query low-rank path, an output gate, or the maps.

The latent kernel at 80 heads does ``80 x 2 x (576 + 512)`` operations on
the ``1152`` bytes of a position: 151 FLOPs a byte against the chip's ~240,
so its roofline is the larger of the two times, not the bytes' alone.
"""

from __future__ import annotations


def full_layers(config: dict) -> int:
    period = config["sliding_window_period"]
    return sum(1 for i in range(config["num_hidden_layers"])
               if i % period == period - 1)


def window_layers(config: dict) -> int:
    return config["num_hidden_layers"] - full_layers(config)


def routed_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["n_dense_first_layers"]


def latent_width(config: dict) -> int:
    """Values one cached position holds in one layer: ``[c | k_rope]``."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """What a cached position costs: the full layers' rows only."""
    return full_layers(config) * latent_width(config) * bytes_per_value


def window_bytes_per_row(config: dict, bytes_per_value: int = 2) -> int:
    """What a slot row carries whatever its length: the window layers'
    rings of ``sliding_window`` positions."""
    return (window_layers(config) * config["sliding_window"]
            * latent_width(config) * bytes_per_value)


def attention_params(config: dict) -> int:
    """One layer's W_dq, W_uq, W_dkv, W_ukv, w_lambda, W_gate and W_o (the
    norms are vectors)."""
    d, h, g = (config["hidden_size"], config["num_attention_heads"],
               config["num_key_value_heads"])
    head, rope = config["head_dim"], config["qk_rope_head_dim"]
    rank, q_rank, v = config["kv_lora_rank"], config["q_lora_rank"], config["v_head_dim"]
    signal = h - config["num_noise_heads"]
    return (d * q_rank + q_rank * h * head + d * (rank + rope)
            + rank * g * (head - rope + v) + d * signal + 2 * d * signal * v)


def mhc_params(config: dict) -> int:
    """A layer's two sub-layers' maps: Phi (float32, counted as two bf16)."""
    n = config["mhc_expansion_rate"]
    return 2 * 2 * n * config["hidden_size"] * (2 * n + n * n)


def expert_params(config: dict) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: dict) -> int:
    return config["num_shared_experts"] * expert_params(config)


def dense_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def router_params(config: dict) -> int:
    """The gate, over all the experts routed over."""
    routed = config.get("published", {}).get("num_experts", config["num_experts"])
    return config["hidden_size"] * routed


def experts_step_min_bytes(config: dict, touched_per_layer: float,
                           bytes_per_param: int = 2) -> float:
    """What the expert part of one decode step has to read: in every routed
    layer the router, the shared expert and the three matrices of each
    *held* routed expert some live row chose (``touched_per_layer``: the
    program's counter, over the experts held)."""
    per_layer = (touched_per_layer * expert_params(config)
                 + shared_params(config) + router_params(config))
    return routed_layers(config) * per_layer * bytes_per_param


def live_positions(config: dict, lengths) -> int:
    """Positions the latent kernel reads in one step over all layers, rows
    ``lengths`` long: a full layer a row's length, a window layer
    ``min(length, sliding_window)``."""
    window = config["sliding_window"]
    return (full_layers(config) * sum(lengths)
            + window_layers(config) * sum(min(n, window) for n in lengths))


def attention_step_min_bytes(config: dict, lengths, bytes_per_value: int = 2) -> float:
    return live_positions(config, lengths) * latent_width(config) * bytes_per_value


def attention_step_flops(config: dict, lengths) -> float:
    """Multiply-adds x 2 of the absorbed form over the live positions: a
    score over the whole row and a value sum over its latent part, a head,
    signal and noise heads alike."""
    per_position = 2 * config["num_attention_heads"] * (
        latent_width(config) + config["kv_lora_rank"])
    return live_positions(config, lengths) * per_position


def decode_step_min_bytes(config: dict, touched_per_layer: float, lengths,
                          bytes_per_param: int = 2) -> float:
    """A whole decode step: every layer's attention projections and maps,
    the dense layers, the head's slice (the embedding is a gather of a few
    rows) once, the expert part, and the live rows."""
    weights = (config["num_hidden_layers"] * (
        attention_params(config) + mhc_params(config))
        + config["n_dense_first_layers"] * dense_params(config)
        + config["hidden_size"] * config["vocab_size"])
    return (weights * bytes_per_param
            + experts_step_min_bytes(config, touched_per_layer, bytes_per_param)
            + attention_step_min_bytes(config, lengths, bytes_per_param))
