"""How late the load generator ran: sent minus due, 99th percentile. A
starved generator must not be read as a fast server: above LIMIT_MS the
run's latencies are the generator's, not the system's."""

from ..harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "load generator", "moves": "tpot_p50_ms"}
LIMIT_MS = 50.0


def read(result):
    if "records" not in result:
        return None
    p99 = stats.percentile(
        stats.lateness(result["records"], 0.0, result["window_s"]), 99)
    return None if p99 is None else p99 * 1000.0
