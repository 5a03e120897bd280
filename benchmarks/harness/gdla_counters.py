"""What the GDLA / mHC readers of ``layer_metrics/`` share: parts of a
traced run's result. Not a metric: it has no ``META`` and ``BENCHMARK.json``
does not name it. Each returns None where the program has no such scope,
kernel or counter (the parent of the PR that added them, and every other
family). The scopes are no part of the expert layers, so the driver keeps
them under ``scopes["attention_scope_s"]``
(``drivers/serve_closed_loop_arch_window_routed.py``)."""

from typing import List, Optional

from . import mla_counters, ssm_counters

GDLA_SCOPE, MHC_SCOPE = "gdla.", "mhc."


def gdla_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends under ``gdla.absorb`` and
    ``gdla.diff``; None for a program without them."""
    return mla_counters.scope_step_s(result, GDLA_SCOPE)


def mhc_step_s(result) -> Optional[float]:
    return mla_counters.scope_step_s(result, MHC_SCOPE)


def step_s(result) -> Optional[float]:
    scopes = ssm_counters.decode_scopes(result)
    if not scopes or not scopes.get("module_s"):
        return None
    return scopes["module_s"] / scopes["executions"]


def live_lengths(result) -> Optional[List[int]]:
    """Each live stream's positions in context half way through the traced
    sub-window, from the client records (``mla_counters.live_tokens`` is
    their sum)."""
    traced = result.get("traced")
    if not traced or "records" not in result:
        return None
    middle = (traced["start"] + traced["stop"]) / 2
    lengths = []
    for r in result["records"]:
        stamps = r["stamps"]
        if stamps and stamps[0] <= middle and (r["done"] or stamps[-1]) >= middle:
            lengths.append(r["prompt_len"] + sum(1 for t in stamps if t <= middle))
    return lengths or None
