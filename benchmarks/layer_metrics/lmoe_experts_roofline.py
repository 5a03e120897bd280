"""The latent expert layers of a decode step against their roofline, for a
configuration that holds a share of each layer's experts: the least time to
read, in every expert layer, the router (all the experts routed over), the
two latent projections, the shared expert and the **two** matrices of each
touched *held* routed expert (the program's ``touched`` counter, which
counts over the experts held, a mean over the run;
``harness/flops_lmoe.py``), each matrix once, at the peak memory bandwidth,
over the device time a step spends under the ``moe.*`` scopes
(``moe.route``, ``moe.latent``, ``moe.experts``, ``moe.shared``). The
grouped kernel's ungated form at a latent of 1024 and an inner width of
2688, one visit a touched expert. The operations' share of the chip's peak
is printed beside it (``emit``), not reported: a visit multiplies a whole
row tile for the few rows that are the expert's."""

from ..harness import cli, flops_lmoe, lmoe_counters, moe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    touched = moe_counters.touched_per_layer(result)
    step_s = lmoe_counters.experts_step_s(result)
    if touched is None or not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]
    config = result["config"]
    rows = lmoe_counters.rows(result)
    counts = moe_counters.delta(result)
    if rows and counts:
        held = sum(sum(layer) for layer in counts["assignments"]) / (
            counts["steps"] * len(counts["assignments"]))
        cli.emit(lmoe_experts_flop_share_pct=100.0 * flops_lmoe.experts_step_flops(
            config, rows[1], held) / peak["bf16_flops_per_s"] / step_s,
            held_assignments_a_layer_a_step=held)
    least_s = flops_lmoe.experts_step_min_bytes(config, touched) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
