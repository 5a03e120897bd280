"""The latent decode kernel's share of its roofline: the least time the
chip could take to read the live latent rows of a step (every position in
context once a layer, ``kv_lora_rank + qk_rope_head_dim`` values:
``harness/flops_mla.py``) at the peak memory bandwidth, over the device
time a step spends in ``latent_decode_attention``. Bandwidth-bound; the
operations' share of the MXU's peak is printed beside it (``emit``), not
reported. What the kernel reads beyond the live rows, a partial last block
a row, counts against it."""

from ..harness import cli, flops_mla, mla_counters

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    live = mla_counters.live_tokens(result)
    step_s = mla_counters.kernel_step_s(result)
    if not live or not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]
    config = result["config"]
    cli.emit(mla_attention_flop_share_pct=100.0 * flops_mla.attention_step_flops(
        config, live) / peak["bf16_flops_per_s"] / step_s, live_tokens=live)
    least_s = flops_mla.attention_step_min_bytes(config, live) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
