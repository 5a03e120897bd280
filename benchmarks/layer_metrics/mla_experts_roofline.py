"""The expert part of a decode step against its roofline, for a
configuration with shared experts and a dense first layer: the least time
to read, in every *routed* layer, the router, the shared experts and the
touched routed experts (the program's counter, a mean over the run;
``harness/flops_mla.py``) at the peak memory bandwidth, over the device
time a step spends under the ``moe.*`` scopes (``moe.route``,
``moe.experts``, ``moe.shared``). The grouped kernel at an inner width of
1408 (``ops/moe_experts.block_f``)."""

from ..harness import cli, flops_mla, mla_counters, moe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    step_s = mla_counters.scope_step_s(result, "moe.")
    if touched is None or not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    least_s = flops_mla.experts_step_min_bytes(result["config"], touched) / peak
    return 100.0 * least_s / step_s
