"""Share of the decode program's device time spent in the KDA mixers: self
time of the ops traced under ``jax.named_scope("kda.proj")`` (``W_qkv``, the
two low-rank gates, ``W_b``, the gated norm and ``W_o``), ``("kda.conv")``
and ``("kda.state")`` (the L2 norms, the decay and the delta rule) over the
program's executions (``harness/xplane_scopes.py``). Reported from the
three scopes' *sum*: a fusion carries the scope of its root, so an op fused
across an edge between two of them is counted on one side, and only the sum
is sound."""

from ..harness import kda_counters, ssm_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    seconds = kda_counters.mixer_s(result)
    if not seconds:
        return None
    return 100.0 * seconds / ssm_counters.decode_scopes(result)["module_s"]
