"""A state-space hybrid's whole decode step against its roofline:
``decode_roofline`` with this family's bytes (``harness/flops_ssm.py``:
every block's weights and the head once, the state and convolution tail of
every row that was a request's in and out, and the keys and values of the
tokens in context) over the median device time of the decode program. The
share counted over every row the program steps, live or free, is printed
beside it (``emit``), not reported (``ssm_state_roofline`` says why)."""

from ..harness import cli, flops_ssm, mla_counters, ssm_counters
from . import decode_step_device_ms

META = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernel", "moves": "tpot_p50_ms"}


def read(result):
    trace = result.get("trace")
    tokens = mla_counters.live_tokens(result)
    rows = ssm_counters.rows(result)
    # (a program without the mixer's scopes is another family's)
    if not trace or not tokens or not rows or not ssm_counters.scan_step_s(result):
        return None
    step_s = decode_step_device_ms.median_s(trace)
    if not step_s:
        return None
    carried, live = rows
    peak = cli.peaks()[result["device"]["kind"]]["hbm_bytes_per_s"]

    def share(stepped):
        return 100.0 * flops_ssm.decode_step_min_bytes(
            result["config"], stepped, tokens) / peak / step_s

    cli.emit(ssm_decode_roofline_carried_rows_pct=share(carried))
    return share(live)
