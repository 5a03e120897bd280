"""Routing imbalance over the run: in each layer the busiest expert's
assignments over the mean expert's (the program's ``assignments`` counter
between the run's two ends), averaged over layers. 1.0 is even; with random
weights and random ids it is sampling noise, and a mix with skewed routing
is what would move it."""

from ..harness import moe_counters

META = {"unit": "ratio", "better": "lower", "source": "program_counter",
        "layer": "expert layer", "moves": "tpot_p50_ms"}


def read(result):
    counts = moe_counters.delta(result)
    if not counts:
        return None
    ratios = [max(row) * len(row) / sum(row)
              for row in counts["assignments"] if sum(row)]
    return sum(ratios) / len(ratios) if ratios else None
