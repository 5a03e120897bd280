"""The latent decode kernel's share of its roofline at 80 heads: the
larger of the least time to read a step's live latent rows at the peak
memory bandwidth and the least time for the 80 heads' operations on them at
the MXU's peak (``harness/flops_gdla.py``: 151 FLOPs a byte, so neither is
small beside the other), over the device time a step spends in
``latent_decode_attention``. Live is a row's length in a full layer and
``min(length, sliding_window)`` in a window layer, from the client records.
Both shares are printed (``emit``). None for a program without the
``gdla.*`` scopes (another family's latent kernel is ``mla_attention_roofline``'s)."""

from ..harness import cli, flops_gdla, gdla_counters, mla_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    lengths = gdla_counters.live_lengths(result)
    kernel_s = mla_counters.kernel_step_s(result)
    if not lengths or not kernel_s or not gdla_counters.gdla_step_s(result):
        return None
    peak = cli.peaks()[result["device"]["kind"]]
    config = result["config"]
    bytes_s = flops_gdla.attention_step_min_bytes(config, lengths) / peak["hbm_bytes_per_s"]
    flops_s = flops_gdla.attention_step_flops(config, lengths) / peak["bf16_flops_per_s"]
    cli.emit(gdla_attention_bytes_share_pct=100.0 * bytes_s / kernel_s,
             gdla_attention_flop_share_pct=100.0 * flops_s / kernel_s,
             live_rows=len(lengths), live_tokens=sum(lengths))
    return 100.0 * max(bytes_s, flops_s) / kernel_s
