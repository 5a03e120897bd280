"""A Motif model's whole decode step against its roofline:
``decode_roofline`` with this family's bytes (``harness/flops_gdla.py``:
every layer's attention projections and maps, the dense layers and the
head's slice once, the router, the shared expert and the touched held
experts of every routed layer, and the live rows: a row's length in the
full layers, ``min(length, sliding_window)`` in the window layers) at the
peak memory bandwidth, over the median device time of the decode program.
None for a program without the ``gdla.*`` scopes."""

from ..harness import cli, flops_gdla, gdla_counters, moe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    lengths = gdla_counters.live_lengths(result)
    touched = moe_counters.touched_per_layer(result)
    if (not trace or not lengths or touched is None
            or not gdla_counters.gdla_step_s(result)):
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * flops_gdla.decode_step_min_bytes(
        result["config"], touched, lengths) / peak / step_s
