"""What the routed-expert readers of ``layer_metrics/`` share: the program's expert counters
(``runtime_info()["moe"]``, kept by the driver under
``result["program_counters"]``: ``decode_steps``, ``assignments``
[layer][expert] by live rows, ``touched`` [layer] = sum over steps of
distinct experts live rows chose) as the difference between the run's two
ends, and the decode step's expert time from the trace. Not a metric: it
has no ``META`` and ``BENCHMARK.json`` does not name it."""

from typing import Optional


def delta(result) -> Optional[dict]:
    """Counts between the run's ends; None where the program keeps none
    or no decode step ran between them."""
    kept = result.get("program_counters") or {}
    after = (kept.get("after") or {}).get("moe")
    before = (kept.get("before") or {}).get("moe")
    if not after:
        return None
    before = before or {
        "decode_steps": 0, "touched": [0] * len(after["touched"]),
        "assignments": [[0] * len(row) for row in after["assignments"]]}
    steps = after["decode_steps"] - before["decode_steps"]
    if steps <= 0:
        return None
    return {
        "steps": steps,
        "touched": [a - b for a, b in zip(after["touched"], before["touched"])],
        "assignments": [
            [a - b for a, b in zip(row_a, row_b)]
            for row_a, row_b in zip(after["assignments"], before["assignments"])],
    }


def touched_per_layer(result) -> Optional[float]:
    """Distinct experts live rows chose, a layer a step."""
    counts = delta(result)
    if not counts:
        return None
    return sum(counts["touched"]) / (counts["steps"] * len(counts["touched"]))


def experts_step_s(result) -> Optional[float]:
    """Device seconds a decode step spends under the ``moe.route`` and
    ``moe.experts`` scopes; None where the trace shows neither."""
    scopes = result.get("scopes")
    if not scopes or not scopes["executions"]:
        return None
    seconds = sum(scopes["scope_s"].values())
    return seconds / scopes["executions"] if seconds > 0 else None
