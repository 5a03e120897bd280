"""The expert part of a decode step against its roofline, for a Motif
configuration that holds a share of each routed layer's experts: the least
time to read, in every routed layer, the router (all the experts routed
over), the shared expert and the touched *held* routed experts (the
program's ``touched`` counter, over the experts held;
``harness/flops_gdla.py``) at the peak memory bandwidth, over the device
time a step spends under the ``moe.*`` scopes. The grouped kernel in its
PolyNorm form at an inner width of 1280: two sweeps of five blocks a
visit, each matrix read once. None for a program without the ``gdla.*``
scopes."""

from ..harness import cli, flops_gdla, gdla_counters, mla_counters, moe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    step_s = mla_counters.scope_step_s(result, "moe.")
    if touched is None or not step_s or not gdla_counters.gdla_step_s(result):
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * flops_gdla.experts_step_min_bytes(
        result["config"], touched) / peak / step_s
