"""Share of the decode program's device time spent in the Mamba-2 mixer
layers of a stack whose layers are a mixer, an attention or an expert layer
alone: self time of the ops traced under ``jax.named_scope("ssm.proj")``
(``W_in``, gate, norm and ``W_out``), ``("ssm.conv")`` and ``("ssm.scan")``
over the program's executions (``harness/xplane_scopes.py``). Reported from
the three scopes' *sum*: a fusion carries the scope of its root, so an op
fused across an edge between two of them is counted on one side, and only
the sum is sound."""

from ..harness import lmoe_counters, ssm_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    seconds = lmoe_counters.mixer_s(result)
    if not seconds:
        return None
    return 100.0 * seconds / ssm_counters.decode_scopes(result)["module_s"]
