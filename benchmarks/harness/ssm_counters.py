"""What the state-space readers of ``layer_metrics/`` share: parts of a
traced run's result. Not a metric: it has no ``META`` and ``BENCHMARK.json``
does not name it. Each returns None where the program has no such scope,
span attribute or counter (the parent of the PR that added them)."""

from typing import Optional, Tuple

from . import hostplane

SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan")
SCAN = "ssm.scan"
DISPATCH = "engine.decode_dispatch"


def decode_scopes(result) -> Optional[dict]:
    """``xplane_scopes.by_name`` of the decode program, where it ran."""
    scopes = result.get("scopes")
    return scopes if scopes and scopes.get("executions") else None


def scan_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends in the recurrence, all layers."""
    scopes = decode_scopes(result)
    seconds = (scopes or {}).get("scope_s", {}).get(SCAN)
    return seconds / scopes["executions"] if seconds else None


def rows(result) -> Optional[Tuple[int, float]]:
    """Over the traced ``engine.decode_dispatch`` spans: the rows whose
    state a step carries, as the program says (``state_rows``: the pool's,
    live or free), and the mean of the rows that were a request's
    (``batch``). A roofline's least bytes are the second's: stepping a free
    row's state is work no request needs."""
    loaded = hostplane.of(result)
    if not loaded:
        return None
    carried = hostplane.counts(loaded, DISPATCH, "state_rows")
    live = hostplane.counts(loaded, DISPATCH, "batch")
    if not carried or not live:
        return None
    return int(max(carried)), sum(live) / len(live)


def kept(result, key: str):
    """``runtime_info()["kv"][key]`` at the end of the run."""
    after = (result.get("program_counters") or {}).get("after") or {}
    return (after.get("kv") or {}).get(key)
