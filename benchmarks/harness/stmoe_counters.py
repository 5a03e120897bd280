"""What the SmallThinker readers of ``layer_metrics/`` share: parts of a
traced run's result. Not a metric: it has no ``META`` and ``BENCHMARK.json``
does not name it. Each returns None where the program has no such scope,
kernel or counter (the parent of the PR that added them, and every other
family: a program without the ``sthink.*`` scopes is another family's). The
attention's scopes are no part of the expert layers, so the driver keeps
them under ``scopes["attention_scope_s"]``
(``drivers/serve_closed_loop_arch_window_routed.py``)."""

from typing import Optional

from . import c2moe_counters, gdla_counters, mla_counters

ATTENTION_SCOPE = "sthink.attn_"
# the router ahead of the attention, and what follows from its choice
# alone in front of the expert kernel (the sort, the group sizes)
ROUTE_SCOPES = ("sthink.route", "moe.sort")
DECODE_KERNEL, EXPERT_KERNEL = "decode_attention", "moe_experts"

# each live stream's positions half way through the traced sub-window, a
# decode step's device seconds, and live rows' choices a layer a step
live_lengths = gdla_counters.live_lengths
step_s = gdla_counters.step_s
assignments_per_layer = c2moe_counters.held_assignments_per_layer


def of_this_family(result) -> bool:
    return bool(mla_counters.scope_step_s(result, ATTENTION_SCOPE))


def route_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends under ``ROUTE_SCOPES``, all
    layers; None for a program without ``sthink.route``."""
    route = mla_counters.scope_step_s(result, ROUTE_SCOPES[0])
    if not route:
        return None
    return route + (mla_counters.scope_step_s(result, ROUTE_SCOPES[1]) or 0.0)


def kernel_step_s(result, kernel: str = DECODE_KERNEL) -> Optional[float]:
    """Device seconds a decode step spends in ``kernel``, all layers, for a
    program of this family."""
    if not of_this_family(result):
        return None
    return mla_counters.kernel_step_s(result, kernel)
