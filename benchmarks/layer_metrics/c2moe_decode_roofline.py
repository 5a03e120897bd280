"""A Cohere2-MoE model's whole decode step against its roofline:
``decode_roofline`` with this family's bytes (``harness/flops_c2moe.py``:
every layer's q/k/v/o, shared experts and router, the tied matrix's slice
once as the head, the touched held experts of every layer, and the live
rows: a row's length in the full layer, ``min(length, sliding_window)`` in
the window layers) at the peak memory bandwidth, over the median device
time of the decode program: the share of the whole step that bounds any
later claim in this cell. None for a program without the ``c2moe.*``
scopes."""

from ..harness import c2moe_counters, cli, flops_c2moe, moe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    lengths = c2moe_counters.live_lengths(result)
    touched = moe_counters.touched_per_layer(result)
    if (not trace or not lengths or touched is None
            or not c2moe_counters.attention_scope_step_s(result)):
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * flops_c2moe.decode_step_min_bytes(
        result["config"], touched, lengths) / peak / step_s
