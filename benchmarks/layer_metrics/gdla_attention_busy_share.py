"""Share of the decode program's device time spent attending, in a GDLA
model: the ``latent_decode_attention`` kernel (one call a layer with all 80
heads, signal and noise, on a full layer's row or a window layer's ring)
plus the ops under ``jax.named_scope("gdla.absorb")`` (the two per-group
projections between the heads' spaces and the latent one) and
``("gdla.diff")`` (the signal heads' latents minus ``lambda`` times their
group's noise head's), over the program's executions. None for a program
without the ``gdla.*`` scopes."""

from ..harness import gdla_counters, mla_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    around_s = gdla_counters.gdla_step_s(result)
    kernel_s = mla_counters.kernel_step_s(result)
    step_s = gdla_counters.step_s(result)
    if not around_s or not kernel_s or not step_s:
        return None
    return 100.0 * (kernel_s + around_s) / step_s
