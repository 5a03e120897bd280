"""90th percentile of time to first token as the client saw it, from when
the request was due, over requests due inside the window."""

from ..harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "ingress and router", "moves": "tpot_p50_ms"}


def read(result):
    if "records" not in result:
        return None
    p90 = stats.percentile(
        stats.ttfts_s(result["records"], 0.0, result["window_s"]), 90)
    return None if p90 is None else p90 * 1000.0
