"""Share of the decode program's device time spent on what a SmallThinker
layer knows before its attention starts: the ops under
``jax.named_scope("sthink.route")`` (the router's float32 matmul on the
attention's input, the top-k and the softmax) and under ``"moe.sort"`` (the
sort of the step's assignments by expert, the group sizes and the way
back, in front of and behind ``moe_experts``), over the program's
executions. None of it reads the attention's output, so it is what a later
change can take off the step's critical path. None for a program without
the ``sthink.*`` scopes."""

from ..harness import stmoe_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    route_s = stmoe_counters.route_step_s(result)
    step_s = stmoe_counters.step_s(result)
    if not route_s or not step_s:
        return None
    return 100.0 * route_s / step_s
