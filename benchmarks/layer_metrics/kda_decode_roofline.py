"""A KDA / GQA / held-experts model's whole decode step against its
roofline: ``decode_roofline`` with this family's bytes
(``harness/flops_kda.py``: every mixer's and gate's weights and the head's
slice once, the state and convolution tail of every row that was a
request's in and out once, the keys and values of the tokens in context in
the GQA layers, and the router, the shared expert and the touched held
experts of every layer) over the median device time of the decode program.
The share counted over every row the program steps, live or free, is
printed beside it (``emit``), not reported (``kda_state_roofline`` says
why)."""

from ..harness import cli, flops_kda, kda_counters, mla_counters, moe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    tokens = mla_counters.live_tokens(result)
    rows = kda_counters.rows(result)
    touched = moe_counters.touched_per_layer(result)
    if (not trace or not tokens or not rows or touched is None
            or not kda_counters.state_step_s(result)):
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    carried, live = rows
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]

    def share(stepped):
        return 100.0 * flops_kda.decode_step_min_bytes(
            result["config"], stepped, tokens, touched) / peak / step_s

    cli.emit(kda_decode_roofline_carried_rows_pct=share(carried))
    return share(live)
