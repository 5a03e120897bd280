"""The expert layers' share of their roofline in a decode step: the least
time the chip could take to read what they need (in every layer the router
and the three matrices of each expert some live row chose; how many that
was comes from the program's counter, a mean over the run) at the peak
memory bandwidth, over the device time a step spends under the
``moe.route`` and ``moe.experts`` scopes. Bandwidth-bound: a touched expert
serves one or two rows (``harness/flops_moe.py``)."""

from ..harness import cli, flops_moe, moe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    step_s = moe_counters.experts_step_s(result)
    if touched is None or not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    least_s = flops_moe.experts_step_min_bytes(result["config"], touched) / peak
    return 100.0 * least_s / step_s
