"""Device time in prefill programs over the prompt tokens prefilled in the
traced sub-window, in milliseconds a thousand tokens. A request's prefill
is counted in the sub-window when its first token arrived in it."""

from ..harness import xplane
from .sched_prefill_busy_share import PREFILL

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    trace, traced = result.get("trace"), result.get("traced")
    if not trace or not traced or "records" not in result:
        return None
    tokens = sum(
        r["prompt_len"] for r in result["records"]
        if r["stamps"] and traced["start"] <= r["stamps"][0] < traced["stop"])
    seconds = xplane.module_total_s(trace, PREFILL)
    if not tokens or not seconds:
        return None
    return seconds * 1000.0 / (tokens / 1000.0)
