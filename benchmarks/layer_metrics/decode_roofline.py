"""The decode step's share of its roofline: the least time the chip could
take to read what a step needs (every matmul weight once, and the keys and
values of the tokens in context) at the peak memory bandwidth, over the
step's measured device time. Bandwidth-bound: a step does 2 FLOPs a weight
byte a sequence, far under the chip's 240 FLOPs a byte."""

from ..harness import cli, flops
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace, traced = result.get("trace"), result.get("traced")
    if not trace or not traced or "records" not in result:
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    middle = (traced["start"] + traced["stop"]) / 2
    live = 0  # tokens in context half way through the traced sub-window
    for r in result["records"]:
        stamps = r["stamps"]
        if stamps and stamps[0] <= middle and (r["done"] or stamps[-1]) >= middle:
            live += r["prompt_len"] + sum(1 for t in stamps if t <= middle)
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    least_s = flops.decode_step_min_bytes(result["config"], live) / peak
    return 100.0 * least_s / step_s
