"""What the KDA readers of ``layer_metrics/`` share: parts of a traced
run's result. Not a metric: it has no ``META`` and ``BENCHMARK.json`` does
not name it. Each returns None where the program has no such scope (the
parent of the PR that added them). The scopes are no part of the expert
layers, so the driver keeps them under ``scopes["attention_scope_s"]``
(``drivers/serve_closed_loop_arch_stateful_routed.py``)."""

from typing import Optional

from . import mla_counters, ssm_counters

SCOPES = ("kda.proj", "kda.conv", "kda.state")
STATE = "kda.state"

# the rows a step carries and the rows that were a request's, from the
# traced ``engine.decode_dispatch`` spans (``state_rows``, ``batch``)
rows = ssm_counters.rows


def mixer_s(result) -> Optional[float]:
    """Device seconds of the whole traced run under the three scopes."""
    scopes = ssm_counters.decode_scopes(result)
    named = (scopes or {}).get("attention_scope_s", {})
    seconds = sum(named.get(name, 0.0) for name in SCOPES)
    return seconds if seconds > 0 else None


def state_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends in the recurrence, all layers."""
    return mla_counters.scope_step_s(result, STATE)
