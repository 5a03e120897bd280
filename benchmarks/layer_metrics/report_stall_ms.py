"""Median time the loop stood in ``train.report``, by the loop's clock."""

from ..harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "report and checkpoint", "moves": "train_tok_per_s_per_chip"}


def read(result):
    stall = stats.median(result.get("report_stalls_s") or [])
    return None if stall is None else stall * 1000.0
