"""The grouped expert kernel of a decode step against its roofline, for a
SmallThinker configuration: the least time to read, in every layer, the
three 2560 x 768 matrices of each *touched* expert once and to move each
assignment's row in and out (the program's ``touched`` and ``assignments``
counters; ``harness/flops_stmoe.py``) at the peak memory bandwidth, over
the device time a step spends in ``moe_experts``. A step's 384 assignments
are three row tiles, and an expert whose rows lie on a tile's edge is
visited, and read, in both: that second read is no part of the least work,
so the share falls by it. None for a program without the ``sthink.*``
scopes."""

from ..harness import cli, flops_stmoe, moe_counters, stmoe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    assigned = stmoe_counters.assignments_per_layer(result)
    kernel_s = stmoe_counters.kernel_step_s(result, stmoe_counters.EXPERT_KERNEL)
    if touched is None or assigned is None or not kernel_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    cli.emit(stmoe_experts_touched_per_layer=touched,
             stmoe_assignments_per_layer=assigned)
    return 100.0 * flops_stmoe.experts_kernel_min_bytes(
        result["config"], touched, assigned) / peak / kernel_s
