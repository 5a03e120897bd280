"""The decode-attention kernel's share of its roofline at 28 query heads on
4 K/V heads: the larger of the least time to read a step's live keys and
values at the peak memory bandwidth and the least time for the 28 heads'
operations on them at the MXU's peak (``harness/flops_stmoe.py``: 7 FLOPs a
byte, so the bytes bound it), over the device time a step spends in
``decode_attention``. Live is ``min(length, sliding_window_size)``
positions in each window layer's ring and a row's length in each full
layer, 2 KB a position a layer, from the client records. Both shares are
printed (``emit``). None for a program without the ``sthink.*`` scopes."""

from ..harness import cli, flops_stmoe, stmoe_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    lengths = stmoe_counters.live_lengths(result)
    kernel_s = stmoe_counters.kernel_step_s(result)
    if not lengths or not kernel_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]
    config = result["config"]
    bytes_s = flops_stmoe.attention_step_min_bytes(config, lengths) / peak["hbm_bytes_per_s"]
    flops_s = flops_stmoe.attention_step_flops(config, lengths) / peak["bf16_flops_per_s"]
    cli.emit(stmoe_attention_bytes_share_pct=100.0 * bytes_s / kernel_s,
             stmoe_attention_flop_share_pct=100.0 * flops_s / kernel_s,
             live_rows=len(lengths), live_tokens=sum(lengths))
    return 100.0 * max(bytes_s, flops_s) / kernel_s
