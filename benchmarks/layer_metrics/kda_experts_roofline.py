"""The expert part of a decode step against its roofline, for a
configuration that holds a share of each layer's experts: the least time
to read, in every layer, the router (all the experts routed over), the
shared expert and the touched *held* routed experts (the program's
``touched`` counter, which counts over the experts held, a mean over the
run; ``harness/flops_kda.py``) at the peak memory bandwidth, over the
device time a step spends under the ``moe.*`` scopes (``moe.route``,
``moe.experts``, ``moe.shared``). The grouped kernel at an inner width of
1280, one visit a touched expert, none for an expert held elsewhere."""

from ..harness import cli, flops_kda, kda_counters, mla_counters, moe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    step_s = mla_counters.scope_step_s(result, "moe.")
    # (a program without the mixer's scopes is another family's)
    if touched is None or not step_s or not kda_counters.mixer_s(result):
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    least_s = flops_kda.experts_step_min_bytes(result["config"], touched) / peak
    return 100.0 * least_s / step_s
