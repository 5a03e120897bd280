"""Operations and bytes of a Nemotron-H-shaped configuration (layers that
are a Mamba-2 mixer, a grouped-query attention or a latent mixture of
experts alone, by ``hybrid_override_pattern``), from its published keys:
``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
``conv_kernel``, the GQA keys, ``moe_latent_size``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``,
``n_routed_experts`` (the experts *held*), ``published.n_routed_experts``
(the router's width) and ``vocab_size`` (the slice held). ``config`` is a
configuration file.

Beside ``flops_ssm.py`` (a mixer beside attention in *every* block, a dense
SwiGLU) and ``flops_kda.py`` (gated experts of three matrices on the
model's width in every layer): neither counts layers by a pattern, an
expert of two matrices, or experts that work in a latent narrower than the
model between two shared projections.

A decode step is bandwidth-bound throughout: the state update does 6
operations on the 8 bytes it moves a state element; a touched expert's two
matrices are read once for the few rows that chose it.
"""

from __future__ import annotations

MIXER, ATTENTION, EXPERTS = "M", "*", "E"


def layers(config: dict, kind: str) -> int:
    """Layers of ``kind`` among the ``num_hidden_layers`` held."""
    return config["hybrid_override_pattern"][
        :config["num_hidden_layers"]].count(kind)


def d_inner(config: dict) -> int:
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def state_elements(config: dict) -> int:
    """Values of one mixer layer's recurrent state a row."""
    return d_inner(config) * config["ssm_state_size"]


def conv_channels(config: dict) -> int:
    """x, B and C: what the convolution runs over."""
    return d_inner(config) + 2 * config["n_groups"] * config["ssm_state_size"]


def in_proj_columns(config: dict) -> int:
    """``W_in``'s columns: z, then x, B and C, then dt a head."""
    return d_inner(config) + conv_channels(config) + config["mamba_num_heads"]


def tail_elements(config: dict) -> int:
    """Values of one mixer layer's convolution tail a row."""
    return (config["conv_kernel"] - 1) * conv_channels(config)


def state_bytes_per_row(config: dict, state_bytes: int = 4,
                        tail_bytes: int = 2) -> int:
    """What a slot row carries whatever its length, all mixer layers: the
    float32 state and the convolution tail."""
    return layers(config, MIXER) * (
        state_elements(config) * state_bytes
        + tail_elements(config) * tail_bytes)


def kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """K and V a position, all attention layers."""
    return (layers(config, ATTENTION) * 2 * config["num_key_value_heads"]
            * config["head_dim"] * bytes_per_value)


def mixer_params(config: dict) -> int:
    """One mixer layer: W_in and W_out, the convolution and its bias, the
    gated norm, dt_bias, A_log and D a head, and the layer's norm."""
    d = config["hidden_size"]
    return (d * in_proj_columns(config) + d_inner(config) * d
            + (config["conv_kernel"] + 1) * conv_channels(config)
            + d_inner(config) + 3 * config["mamba_num_heads"] + d)


def attention_params(config: dict) -> int:
    """One attention layer's W_q, W_k, W_v, W_o and its norm."""
    d, width = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * d * h * width + 2 * d * hk * width + d


def expert_params(config: dict) -> int:
    """One routed expert's up and down matrices, in the latent."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def shared_params(config: dict) -> int:
    """An expert layer outside its routed experts: the router and its
    selection bias over all the experts routed over, the two latent
    projections, the shared expert's two matrices, the layer's norm."""
    d = config["hidden_size"]
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    return ((d + 1) * routed + 2 * d * config["moe_latent_size"]
            + config["n_shared_experts"] * 2 * d
            * config["moe_shared_expert_intermediate_size"] + d)


def state_step_bytes(config: dict, rows: float, state_bytes: int = 4) -> float:
    """What the recurrence of one decode step has to move: every stepped
    row's state in and out once, in every mixer layer (its other operands
    are a few KB a row)."""
    return 2 * rows * layers(config, MIXER) * state_elements(config) * state_bytes


def state_step_flops(config: dict, rows: float) -> float:
    """Decay, the outer product's multiply-add and the readout's
    multiply-add a state element."""
    return 6 * rows * layers(config, MIXER) * state_elements(config)


def experts_step_min_bytes(config: dict, touched_per_layer: float,
                           bytes_per_param: int = 2) -> float:
    """What the expert layers of one decode step have to read: in each the
    router, the two latent projections, the shared expert, and the two
    matrices of each *held* routed expert some live row chose
    (``touched_per_layer``: the program's counter, over the experts held),
    each matrix once."""
    per_layer = shared_params(config) + touched_per_layer * expert_params(config)
    return layers(config, EXPERTS) * per_layer * bytes_per_param


def experts_step_flops(config: dict, rows: float, held_assignments: float) -> float:
    """Multiply-adds x 2 of the expert layers of a step of ``rows`` rows
    of which ``held_assignments`` (a layer) fell on experts held: router,
    latent projections and shared expert a row, an expert's two matrices
    an assignment."""
    d, latent = config["hidden_size"], config["moe_latent_size"]
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    per_row = (d * routed + 2 * d * latent + config["n_shared_experts"] * 2 * d
               * config["moe_shared_expert_intermediate_size"])
    return 2 * layers(config, EXPERTS) * (
        rows * per_row + held_assignments * expert_params(config))


def decode_step_min_bytes(config: dict, rows: float, live_tokens: int,
                          touched_per_layer: float,
                          bytes_per_param: int = 2) -> float:
    """A whole decode step of ``rows`` live rows at ``live_tokens``
    positions in context over all of them: every mixer's and attention
    layer's weights, the final norm and the head's slice once (the
    embedding is a gather of a few rows), the live rows' state and
    convolution tails in and out, the live keys and values, and the expert
    layers."""
    weights = (layers(config, MIXER) * mixer_params(config)
               + layers(config, ATTENTION) * attention_params(config)
               + config["hidden_size"]
               + config["hidden_size"] * config["vocab_size"])
    tails = 2 * rows * layers(config, MIXER) * tail_elements(config)
    return (weights * bytes_per_param + state_step_bytes(config, rows)
            + tails * bytes_per_param
            + live_tokens * kv_bytes_per_token(config, bytes_per_param)
            + experts_step_min_bytes(config, touched_per_layer, bytes_per_param))
