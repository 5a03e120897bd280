"""A mixer / attention / latent-experts stack's whole decode step against
its roofline: ``decode_roofline`` with this family's bytes
(``harness/flops_lmoe.py``: every mixer's and attention layer's weights and
the head's slice once, the state and convolution tail of every row that was
a request's in and out once, the keys and values of the tokens in context
in the attention layers, and in every expert layer the router, the latent
projections, the shared expert and the touched held experts' two matrices)
over the median device time of the decode program. The share counted over
every row the program steps, live or free, is printed beside it (``emit``),
not reported."""

from ..harness import cli, flops_lmoe, lmoe_counters, mla_counters, moe_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    tokens = mla_counters.live_tokens(result)
    rows = lmoe_counters.rows(result)
    touched = moe_counters.touched_per_layer(result)
    if (not trace or not tokens or not rows or touched is None
            or not lmoe_counters.scan_step_s(result)):
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    carried, live = rows
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]

    def share(stepped):
        return 100.0 * flops_lmoe.decode_step_min_bytes(
            result["config"], stepped, tokens, touched) / peak / step_s

    cli.emit(lmoe_decode_roofline_carried_rows_pct=share(carried))
    return share(live)
