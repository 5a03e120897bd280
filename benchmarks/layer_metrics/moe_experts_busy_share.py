"""Share of the decode program's device time spent in the routed expert
layers: self time of the ops traced under ``jax.named_scope("moe.route")``
(router, softmax, top-k) and ``("moe.experts")`` (sort, gathers, the
grouped kernel, the weighted sum) over the program's executions
(``harness/xplane_scopes.py``)."""

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted program", "moves": "tpot_p50_ms"}


def read(result):
    scopes = result.get("scopes")
    if not scopes or not scopes["module_s"]:
        return None
    seconds = sum(scopes["scope_s"].values())
    return 100.0 * seconds / scopes["module_s"] if seconds > 0 else None
