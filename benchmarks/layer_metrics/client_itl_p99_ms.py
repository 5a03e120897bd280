"""99th percentile of the gap between two tokens of one stream, as the
client saw it, gaps pooled over all streams in the window."""

from ..harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "ingress and router", "moves": "tpot_p50_ms"}


def read(result):
    if "records" not in result:
        return None
    p99 = stats.percentile(
        stats.inter_token_gaps(result["records"], 0.0, result["window_s"]), 99)
    return None if p99 is None else p99 * 1000.0
