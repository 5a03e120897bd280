"""Share of the device's busy time spent in prefill programs."""

from ..harness import xplane

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}
PREFILL = "_prefill_impl"


def read(result):
    trace = result.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * xplane.module_total_s(trace, PREFILL) / trace["busy_s"]
