"""A routed model's decode step against its roofline: ``decode_roofline``
with the bytes a mixture of experts has to read (``harness/flops_moe.py``:
attention projections, the head, the router and the *touched* experts of
every layer, and the keys and values of the tokens in context) in place of
every matmul weight once. Touched experts a layer a step from the
program's counter (a mean over the run), live tokens from the client
records half way through the traced sub-window, as ``decode_roofline``
counts them."""

from ..harness import cli, flops_moe, moe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace, traced = result.get("trace"), result.get("traced")
    touched = moe_counters.touched_per_layer(result)
    if not trace or not traced or touched is None or "records" not in result:
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    middle = (traced["start"] + traced["stop"]) / 2
    live = 0
    for r in result["records"]:
        stamps = r["stamps"]
        if stamps and stamps[0] <= middle and (r["done"] or stamps[-1]) >= middle:
            live += r["prompt_len"] + sum(1 for t in stamps if t <= middle)
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    least_s = flops_moe.decode_step_min_bytes(result["config"], touched, live) / peak
    return 100.0 * least_s / step_s
