"""A latent-attention model's whole decode step against its roofline:
``moe_decode_roofline`` with this family's bytes (``harness/flops_mla.py``:
attention projections, the dense first layer, the head, router + shared +
touched experts of every routed layer, and the latent rows of the tokens in
context) over the median device time of the decode program."""

from ..harness import cli, flops_mla, mla_counters, moe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    touched = moe_counters.touched_per_layer(result)
    live = mla_counters.live_tokens(result)
    if not trace or touched is None or not live:
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    least_s = flops_mla.decode_step_min_bytes(result["config"], touched, live) / peak
    return 100.0 * least_s / step_s
