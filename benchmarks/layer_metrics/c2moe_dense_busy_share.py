"""Share of the decode program's device time spent on what a Cohere2-MoE
layer reads whatever is routed: q/k/v/o (the ops under
``jax.named_scope("c2moe.attn_window")`` / ``("c2moe.attn_full")`` less the
``decode_attention`` and ``kv_row_write`` kernels inside them) and the four
shared experts (``"moe.shared"``), 0.69 GB a layer, over the program's
executions. On one chip of the eight that share a layer this part is whole
while the routed experts are an eighth, so it is eight times its share of a
deployment's step. None for a program without the ``c2moe.*`` scopes."""

from ..harness import c2moe_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    dense_s = c2moe_counters.dense_step_s(result)
    step_s = c2moe_counters.step_s(result)
    if not dense_s or not step_s:
        return None
    return 100.0 * dense_s / step_s
