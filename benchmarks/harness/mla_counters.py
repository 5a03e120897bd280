"""What the latent-attention readers of ``layer_metrics/`` share: parts of
a traced run's result. Not a metric: it has no ``META`` and
``BENCHMARK.json`` does not name it. Each returns None where the program
has no such scope, kernel or counter (the parent of the PR that added
them)."""

from typing import Optional

LATENT_KERNEL = "latent_decode_attention"
ABSORB_SCOPE = "mla.absorb"


def _scopes(result) -> Optional[dict]:
    scopes = result.get("scopes")
    return scopes if scopes and scopes["executions"] else None


def kernel_step_s(result, kernel: str = LATENT_KERNEL) -> Optional[float]:
    """Device seconds a decode step spends in ``kernel``, all layers."""
    scopes = _scopes(result)
    seconds = (scopes or {}).get("kernel_s", {}).get(kernel)
    return seconds / scopes["executions"] if seconds else None


def scope_step_s(result, prefix: str) -> Optional[float]:
    """Device seconds a decode step spends under the scopes whose names
    start with ``prefix``: the expert layers' in ``scope_s``, attention's
    in ``attention_scope_s`` (the reference's ``ATTENTION_SCOPES``)."""
    scopes = _scopes(result)
    if not scopes:
        return None
    named = dict(scopes["scope_s"], **scopes.get("attention_scope_s", {}))
    seconds = sum(s for name, s in named.items() if name.startswith(prefix))
    return seconds / scopes["executions"] if seconds > 0 else None


def live_tokens(result) -> Optional[int]:
    """Positions in context over all streams half way through the traced
    sub-window, from the client records, as ``moe_decode_roofline`` counts
    them."""
    traced = result.get("traced")
    if not traced or "records" not in result:
        return None
    middle = (traced["start"] + traced["stop"]) / 2
    live = 0
    for r in result["records"]:
        stamps = r["stamps"]
        if stamps and stamps[0] <= middle and (r["done"] or stamps[-1]) >= middle:
            live += r["prompt_len"] + sum(1 for t in stamps if t <= middle)
    return live or None


def cache_bytes_per_token(result) -> Optional[int]:
    kept = (result.get("program_counters") or {}).get("after") or {}
    return (kept.get("kv") or {}).get("cache_bytes_per_token")
