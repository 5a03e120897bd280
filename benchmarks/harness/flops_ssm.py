"""Operations and bytes of a Falcon-H1-shaped configuration (in every block
a grouped-query attention and a Mamba-2 mixer side by side, then a SwiGLU),
from its published keys: ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``, ``mamba_d_ssm``,
``intermediate_size`` and the GQA keys. ``config`` is a configuration file.

Beside ``flops.py`` (a dense Llama layer), ``flops_moe.py`` and
``flops_mla.py``: none of them counts a mixer, or a state that a step reads
and writes whole whatever the row's length.

A decode step is bandwidth-bound throughout. The state update does ``6``
operations on the ``8`` bytes it moves a state element (read, decay,
add the outer product, write, and the readout's multiply-add): under one
FLOP a byte against the chip's 240.
"""

from __future__ import annotations

def state_elements(config: dict) -> int:
    """Values of one layer's recurrent state a row: heads x head x state."""
    return config["mamba_n_heads"] * config["mamba_d_head"] * config["mamba_d_state"]


def conv_channels(config: dict) -> int:
    """x, B and C: what the convolution runs over."""
    return config["mamba_d_ssm"] + 2 * config["mamba_n_groups"] * config["mamba_d_state"]


def in_proj_columns(config: dict) -> int:
    """``W_in``'s columns: z, then x, B and C, then dt a head."""
    return config["mamba_d_ssm"] + conv_channels(config) + config["mamba_n_heads"]


def tail_elements(config: dict) -> int:
    """Values of one layer's convolution tail a row: its last
    ``mamba_d_conv - 1`` inputs a channel."""
    return (config["mamba_d_conv"] - 1) * conv_channels(config)


def state_bytes_per_row(config: dict, state_bytes: int = 4,
                        tail_bytes: int = 2) -> int:
    """What a slot row carries whatever its length, all layers: the float32
    state and the convolution tail."""
    return config["num_hidden_layers"] * (
        state_elements(config) * state_bytes
        + tail_elements(config) * tail_bytes)


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """K and V a position, all layers."""
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
            * config["head_dim"] * bytes_per_value)


def attention_params(config: dict) -> int:
    """One block's W_q, W_k, W_v and W_o."""
    d, width = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * width + 2 * d * hk * width + h * width * d


def mixer_params(config: dict) -> int:
    """One block's W_in and W_out, the convolution and its bias, the gated
    norm, and dt_bias, A_log and D a head."""
    d, d_ssm = config["hidden_size"], config["mamba_d_ssm"]
    return (d * in_proj_columns(config) + d_ssm * d
            + (config["mamba_d_conv"] + 1) * conv_channels(config)
            + d_ssm + 3 * config["mamba_n_heads"])


def mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def block_params(config: dict) -> int:
    """One block with its two norms."""
    return (attention_params(config) + mixer_params(config) + mlp_params(config)
            + 2 * config["hidden_size"])


def state_step_bytes(config: dict, rows: int, state_bytes: int = 4) -> int:
    """What the recurrence of one decode step has to move: every stepped
    row's state in and out, in every layer (its other operands are a few
    KB a row)."""
    return 2 * rows * config["num_hidden_layers"] * state_elements(config) * state_bytes


def state_step_flops(config: dict, rows: int) -> int:
    """Decay, the outer product's multiply-add and the readout's
    multiply-add a state element."""
    return 6 * rows * config["num_hidden_layers"] * state_elements(config)


def decode_step_min_bytes(config: dict, rows: int, live_tokens: int,
                          bytes_per_param: int = 2) -> int:
    """A whole decode step of ``rows`` stepped rows at ``live_tokens``
    positions in context over all of them: every block's weights and the
    output head once (the embedding is a gather of a few rows), the states
    in and out, the rows' convolution tails in and out, and the live keys
    and values."""
    weights = (config["num_hidden_layers"] * block_params(config)
               + config["hidden_size"] * config["vocab_size"])
    tails = 2 * rows * config["num_hidden_layers"] * tail_elements(config)
    return (weights * bytes_per_param + state_step_bytes(config, rows)
            + tails * bytes_per_param
            + live_tokens * kv_bytes_per_token(config, bytes_per_param))


def prefill_flops(config: dict, tokens: int) -> int:
    """Multiply-adds x 2 of a prefill of ``tokens`` positions into a fresh
    row: the projections, causal attention, the mixer's chunked form at
    ``mamba_chunk_size`` (inside a chunk of L positions: C B^T over a
    group's state, the masked product with dt x over a head, the chunk's
    state from its inputs and its outputs from the entering state), the
    SwiGLU, and the head at the last position."""
    layers, d = config["num_hidden_layers"], config["hidden_size"]
    chunk = config["mamba_chunk_size"]
    chunks = -(-tokens // chunk)
    h, width = config["num_attention_heads"], config["head_dim"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    d_ssm = config["mamba_d_ssm"]
    projections = 2 * tokens * (
        attention_params(config) + d * in_proj_columns(config) + d_ssm * d
        + mlp_params(config))
    attention = 2 * 2 * h * width * tokens * (tokens + 1) // 2
    within = 2 * chunks * chunk * chunk * (groups * state + d_ssm)
    across = 2 * 2 * chunks * chunk * d_ssm * state
    return layers * (projections + attention + within + across) + 2 * d * config["vocab_size"]
