"""The Mamba-2 recurrence's share of its roofline in a decode step of a
stack whose mixer layers stand alone: the least time the chip could take to
read and write, once, the state of the rows that were a request's (the mean
``batch`` of the traced ``engine.decode_dispatch`` spans;
``harness/flops_lmoe.py``: ``mamba_num_heads x mamba_head_dim x
ssm_state_size`` float32 a row a mixer layer) at the peak memory bandwidth,
over the device time a step spends under ``jax.named_scope("ssm.scan")``,
all mixer layers. The program steps every row of its pool, live or free
(the span's ``state_rows``): the share counted over those is printed beside
it (``emit``), and so is the operations' share of the chip's peak; neither
is reported."""

from ..harness import cli, flops_lmoe, lmoe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    step_s = lmoe_counters.scan_step_s(result)
    rows = lmoe_counters.rows(result)
    if not step_s or not rows:
        return None
    carried, live = rows
    peak = cli.peaks()[result["device"]["kind"]]
    config = result["config"]

    def share(stepped):
        return 100.0 * flops_lmoe.state_step_bytes(
            config, stepped) / peak["hbm_bytes_per_s"] / step_s

    cli.emit(state_rows=carried, live_rows=live,
             hssm_state_roofline_carried_rows_pct=share(carried),
             hssm_state_flop_share_pct=100.0 * flops_lmoe.state_step_flops(
                 config, carried) / peak["bf16_flops_per_s"] / step_s)
    return share(live)
