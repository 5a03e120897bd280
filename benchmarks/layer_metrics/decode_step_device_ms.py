"""Median device duration of one execution of the decode program."""

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}
DECODE = "_decode_impl"


def median_s(trace):
    runs = [m for name, m in trace["modules"].items() if DECODE in name]
    if not runs:
        return None
    return max(runs, key=lambda m: m["count"])["median_s"]


def read(result):
    trace = result.get("trace")
    seconds = median_s(trace) if trace else None
    return None if seconds is None else seconds * 1000.0
