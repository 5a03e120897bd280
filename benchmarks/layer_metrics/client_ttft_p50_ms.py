"""Median time to first token as the client saw it, from when the request
was due: recorded, not judged. Under Poisson arrivals the 25 requests of a
``mistral7b-chat-steady`` window give a median that spreads by 5-7% from run
to run (PERF.md, PR 22), which no bound of 10% or less admits. First tokens
and token gaps both wait for the replica's executor and the engine lock, so
it moves with ``tpot_p50_ms``."""

from ..harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "ingress and router", "moves": "tpot_p50_ms"}


def read(result):
    if "records" not in result:
        return None
    p50 = stats.median(stats.ttfts_s(result["records"], 0.0, result["window_s"]))
    return None if p50 is None else p50 * 1000.0
