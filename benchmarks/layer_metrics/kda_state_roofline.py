"""The delta rule's share of its roofline in a decode step: the least time
the chip could take to read and write, **once**, the state and the
convolution tail of the rows that were a request's (the mean ``batch`` of
the traced ``engine.decode_dispatch`` spans; ``harness/flops_kda.py``) at
the peak memory bandwidth, over the device time a step spends under
``jax.named_scope("kda.state")``. The rule needs ``S'^T k`` before it can
write ``S_t``: a form that reads the state twice moves half as much again
and cannot pass two thirds. The program steps every row of its pool, live
or free (the span's ``state_rows``): the share counted over those is
printed beside it (``emit``), and so is the operations' share of the chip's
peak (bandwidth-bound: about one operation a byte); neither is reported."""

from ..harness import cli, flops_kda, kda_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    step_s = kda_counters.state_step_s(result)
    rows = kda_counters.rows(result)
    if not step_s or not rows:
        return None
    carried, live = rows
    peak = cli.peaks()[result["device"]["kind"]]
    config = result["config"]

    def share(stepped):
        return 100.0 * flops_kda.state_step_bytes(
            config, stepped) / peak["hbm_bytes_per_s"] / step_s

    cli.emit(state_rows=carried, live_rows=live,
             kda_state_roofline_carried_rows_pct=share(carried),
             kda_state_flop_share_pct=100.0 * flops_kda.state_step_flops(
                 config, carried) / peak["bf16_flops_per_s"] / step_s)
    return share(live)
