"""Share of the decode program's device time spent in the decode-attention
kernel, in a Cohere2-MoE model: ``decode_attention``, one call a layer with
all 128 query heads on 8 K/V heads, on a window layer's ring (up to 4096
slots a row) or a full layer's row, over the program's executions. None for
a program without the ``c2moe.*`` scopes."""

from ..harness import c2moe_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    kernel_s = c2moe_counters.kernel_step_s(result)
    step_s = c2moe_counters.step_s(result)
    if not kernel_s or not step_s:
        return None
    return 100.0 * kernel_s / step_s
