"""Operations and bytes a configuration needs, computed from its shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Copied from ``bench.py main()``'s arithmetic for LoRA on a frozen
base (listed in PERF.md, Open questions, for a later PR to delete there).
"""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication: every block's
    projections and the output head, not the embedding (a gather)."""
    d, f, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    per_layer = d * d * 2 + d * kv * 2 + 3 * d * f
    return config["num_hidden_layers"] * per_layer + d * v


def total_params(config: dict) -> int:
    norms = (2 * config["num_hidden_layers"] + 1) * config["hidden_size"]
    return (matmul_params(config) + norms
            + config["vocab_size"] * config["hidden_size"])


def lora_train_model_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs a token for LoRA on a frozen base, recomputation not
    counted: forward 2N and the backward's activation gradients 2N (a frozen
    weight has no weight gradient; the adapters' own are under 0.1% and left
    out), plus causal attention, forward 4 and backward 8 times
    seq x dim a layer, halved by the mask."""
    attention = 12 * config["num_hidden_layers"] * config["hidden_size"] * seq * 0.5
    return 4.0 * matmul_params(config) + attention


def weight_bytes(config: dict, bytes_per_param: int = 2) -> int:
    return total_params(config) * bytes_per_param


def decode_step_min_bytes(config: dict, live_tokens: int,
                          bytes_per_value: int = 2) -> int:
    """What one decode step has to read: every matmul weight once, and the
    keys and values of the tokens in context (``live_tokens``, summed over
    the batch). Bandwidth-bound at these batch sizes: 2 FLOPs a weight byte
    a sequence against the chip's 240 FLOPs a byte."""
    kv_per_token = (2 * config["num_key_value_heads"] * config["head_dim"]
                    * config["num_hidden_layers"] * bytes_per_value)
    return matmul_params(config) * bytes_per_value + live_tokens * kv_per_token
