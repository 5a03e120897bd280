"""Share of the decode program's device time spent attending over the
latent cache: the ``latent_decode_attention`` kernel plus the ops under
``jax.named_scope("mla.absorb")`` (the two per-head projections between
the heads' spaces and the latent one that the absorbed form adds), over the
program's executions. By bytes it should be the live rows' share of a step
(~6% in ``moonlight-longctx-backlog``); more means the kernel or the
absorption is slow, not that attention is large."""

from ..harness import mla_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    scopes = result.get("scopes")
    kernel_s = mla_counters.kernel_step_s(result)
    if not kernel_s or not scopes["module_s"]:
        return None
    absorb_s = mla_counters.scope_step_s(result, mla_counters.ABSORB_SCOPE) or 0.0
    step_s = scopes["module_s"] / scopes["executions"]
    return 100.0 * (kernel_s + absorb_s) / step_s
