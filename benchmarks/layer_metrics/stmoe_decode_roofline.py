"""A SmallThinker model's whole decode step against its roofline:
``decode_roofline`` with this family's bytes (``harness/flops_stmoe.py``:
every layer's q/k/v/o and router, the untied head once, the touched experts
of every layer once and their assignments' rows in and out, and the live
rows: a row's length in a full layer, ``min(length, sliding_window_size)``
in a window layer) at the peak memory bandwidth, over the median device
time of the decode program: the share of the whole step that bounds any
later claim in this cell. None for a program without the ``sthink.*``
scopes."""

from ..harness import cli, flops_stmoe, moe_counters, stmoe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    lengths = stmoe_counters.live_lengths(result)
    touched = moe_counters.touched_per_layer(result)
    assigned = stmoe_counters.assignments_per_layer(result)
    if (not trace or not lengths or touched is None or assigned is None
            or not stmoe_counters.of_this_family(result)):
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * flops_stmoe.decode_step_min_bytes(
        result["config"], touched, assigned, lengths) / peak / step_s
