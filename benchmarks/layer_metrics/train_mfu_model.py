"""Model FLOP/s utilisation: the FLOPs the forward and backward passes need
a token (recomputation not counted, ``harness/flops.py``) times the tokens
of a step over the median step time by the loop's clock (each step ends in
``block_until_ready``), over chips times the peak. From step times and not
from the run's tokens a second, which in a traced run also holds the
profiler's own stalls. An end-to-end utilisation, not a kernel's roofline
share."""

from ..harness import cli, flops, stats

META = {"unit": "%", "better": "higher", "source": "host_clock",
        "layer": "step program", "moves": "train_tok_per_s_per_chip"}


def read(result):
    step_s = stats.median(s["seconds"] for s in result.get("steps") or [])
    if not step_s:
        return None
    peak = cli.peaks()[result["device"]["kind"]]["bf16_flops_per_s"]
    per_token = flops.lora_train_model_flops_per_token(
        result["config"], result["mix"]["seq"])
    tokens_per_s = result["tokens_per_step"] / step_s
    return 100.0 * per_token * tokens_per_s / (result["chips"] * peak)
