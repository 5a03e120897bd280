"""Share of the decode program's device time spent in the Mamba-2 mixers:
self time of the ops traced under ``jax.named_scope("ssm.proj")`` (``W_in``,
the column scaling, gate, norm and ``W_out``), ``("ssm.conv")`` and
``("ssm.scan")`` (the state update) over the program's executions
(``harness/xplane_scopes.py``). Reported from the three scopes' *sum*: a
fusion carries the scope of its root, so an op fused across an edge between
two of them is counted on one side, and only the sum is sound."""

from ..harness import ssm_counters

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    scopes = ssm_counters.decode_scopes(result)
    if not scopes or not scopes["module_s"]:
        return None
    seconds = sum(scopes["scope_s"].get(name, 0.0) for name in ssm_counters.SCOPES)
    return 100.0 * seconds / scopes["module_s"] if seconds > 0 else None
