"""Sequences a decode step carried: tokens streamed in the traced sub-window
over executions of the decode program in it. Each request's first token
comes from its prefill, not from a decode step, and is left out."""

from .decode_step_device_ms import DECODE

META = {"unit": "seqs", "better": "higher", "source": "device_trace",
        "layer": "engine scheduler", "moves": "out_tok_per_s"}


def read(result):
    trace, traced = result.get("trace"), result.get("traced")
    if not trace or not traced or "records" not in result:
        return None
    steps = sum(m["count"] for name, m in trace["modules"].items() if DECODE in name)
    if not steps:
        return None
    tokens = sum(
        1 for r in result["records"] for t in r["stamps"][1:]
        if traced["start"] <= t < traced["stop"])
    return tokens / steps
