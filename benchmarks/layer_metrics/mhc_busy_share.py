"""Share of the decode program's device time spent on the four-stream
residual: self time of the ops traced under ``jax.named_scope("mhc.maps")``
(the norm of the 16384 stream values, their projection to 24, the sigmoids
and the twenty Sinkhorn iterations) and ``("mhc.mix")`` (``H_pre X`` into a
sub-layer, ``H_res X + H_post^T y`` out of it), sixteen sub-layers a step,
over the program's executions. By bytes it should be small (the streams are
4 x 8 KB a row); more means launches, not traffic. None for a program
without the scopes."""

from ..harness import gdla_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    seconds = gdla_counters.mhc_step_s(result)
    step_s = gdla_counters.step_s(result)
    if not seconds or not step_s:
        return None
    return 100.0 * seconds / step_s
