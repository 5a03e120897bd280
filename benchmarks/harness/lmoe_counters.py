"""What the Nemotron-H readers of ``layer_metrics/`` share: parts of a
traced run's result. Not a metric: it has no ``META`` and ``BENCHMARK.json``
does not name it. Each returns None where the program has no such scope (the
parent of the PR that added them, and every other family: a program whose
expert layers have no ``moe.latent`` scope, or whose mixer's scopes are not
kept apart from the expert layers', is another family's). The mixer's scopes
are no part of the expert layers, so the driver keeps them under
``scopes["attention_scope_s"]``
(``drivers/serve_closed_loop_arch_stateful_routed.py``)."""

from typing import Optional

from . import mla_counters, ssm_counters

MIXER_SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan")
SCAN = "ssm.scan"
LATENT = "moe.latent"

# the rows a step carries and the rows that were a request's, from the
# traced ``engine.decode_dispatch`` spans (``state_rows``, ``batch``)
rows = ssm_counters.rows


def mixer_s(result) -> Optional[float]:
    """Device seconds of the whole traced run under the mixer's three
    scopes, for a program that also has latent expert layers."""
    scopes = ssm_counters.decode_scopes(result)
    if not scopes or not scopes.get("scope_s", {}).get(LATENT):
        return None
    named = scopes.get("attention_scope_s", {})
    seconds = sum(named.get(name, 0.0) for name in MIXER_SCOPES)
    return seconds if seconds > 0 else None


def scan_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends in the recurrence, all mixer
    layers."""
    if not mixer_s(result):
        return None
    return mla_counters.scope_step_s(result, SCAN)


def experts_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends under the ``moe.*`` scopes
    (``moe.route``, ``moe.latent``, ``moe.experts``, ``moe.shared``)."""
    if not mixer_s(result):
        return None
    return mla_counters.scope_step_s(result, "moe.")
