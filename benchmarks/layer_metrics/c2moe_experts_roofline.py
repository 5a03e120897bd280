"""The grouped expert kernel of a decode step against its roofline, for a
Cohere2-MoE configuration that holds a share of each layer's experts: the
least time to read, in every layer, the three 4096 x 4096 matrices of each
touched *held* expert and to move each held assignment's row in and out
(the program's ``touched`` and ``assignments`` counters, over the experts
held; ``harness/flops_c2moe.py``) at the peak memory bandwidth, over the
device time a step spends in ``moe_experts``. None for a program without
the ``c2moe.*`` scopes."""

from ..harness import c2moe_counters, cli, flops_c2moe, moe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    held = c2moe_counters.held_assignments_per_layer(result)
    kernel_s = c2moe_counters.kernel_step_s(result, c2moe_counters.EXPERT_KERNEL)
    if touched is None or held is None or not kernel_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]
    cli.emit(c2moe_experts_touched_per_layer=touched,
             c2moe_held_assignments_per_layer=held)
    return 100.0 * flops_c2moe.experts_kernel_min_bytes(
        result["config"], touched, held) / peak / kernel_s
