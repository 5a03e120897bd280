"""What the Cohere2-MoE readers of ``layer_metrics/`` share: parts of a
traced run's result. Not a metric: it has no ``META`` and ``BENCHMARK.json``
does not name it. Each returns None where the program has no such scope,
kernel or counter (the parent of the PR that added them, and every other
family: a program without the ``c2moe.*`` scopes is another family's). The
attention's scopes are no part of the expert layers, so the driver keeps
them under ``scopes["attention_scope_s"]``
(``drivers/serve_closed_loop_arch_window_routed.py``)."""

from typing import Optional

from . import gdla_counters, mla_counters, moe_counters

ATTENTION_SCOPE = "c2moe.attn_"
DECODE_KERNEL, WRITE_KERNEL, EXPERT_KERNEL = (
    "decode_attention", "kv_row_write", "moe_experts")
SHARED_SCOPE = "moe.shared"

# each live stream's positions half way through the traced sub-window, and
# a decode step's device seconds
live_lengths = gdla_counters.live_lengths
step_s = gdla_counters.step_s


def attention_scope_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends under ``c2moe.attn_window`` and
    ``c2moe.attn_full`` (q/k/v/o, the rotary turn, the row write and the
    decode kernel); None for a program without them."""
    return mla_counters.scope_step_s(result, ATTENTION_SCOPE)


def kernel_step_s(result, kernel: str = DECODE_KERNEL) -> Optional[float]:
    """Device seconds a decode step spends in ``kernel``, all layers, for a
    program of this family."""
    if not attention_scope_step_s(result):
        return None
    return mla_counters.kernel_step_s(result, kernel)


def dense_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends on what a layer reads whatever
    is routed: the attention scopes less their two kernels (q/k/v/o and
    the rotary turn are what is left) and the shared experts."""
    around = attention_scope_step_s(result)
    shared = mla_counters.scope_step_s(result, SHARED_SCOPE)
    if not around or not shared:
        return None
    kernels = sum(kernel_step_s(result, k) or 0.0
                  for k in (DECODE_KERNEL, WRITE_KERNEL))
    return max(around - kernels, 0.0) + shared


def held_assignments_per_layer(result) -> Optional[float]:
    """Live rows' choices that fell on a held expert, a layer a step, from
    the step's own counters."""
    counts = moe_counters.delta(result)
    if not counts:
        return None
    held = sum(sum(row) for row in counts["assignments"])
    return held / (counts["steps"] * len(counts["assignments"]))
