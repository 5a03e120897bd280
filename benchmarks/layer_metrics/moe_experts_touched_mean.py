"""Experts, of a layer's ``num_experts``, that a decode step's live rows
chose between them: the program's ``touched`` counter over its decode steps
and layers, between the run's two ends. What a step has to read of the
expert weights is this many experts a layer; with 8 live rows choosing 8 of
64 evenly it is 64 x (1 - (63/64)^64) = 40.6. Neither direction is better
in itself: fewer means fewer bytes a step and more skew. ``better`` has to
be one of two words; ``lower`` stands for "fewer bytes a step" and is for
reading beside ``moe_expert_load_max_over_mean``, not for judging."""

from ..harness import moe_counters

META = {"unit": "experts", "better": "lower", "source": "program_counter",
        "layer": "expert layer", "moves": "tpot_p50_ms"}


def read(result):
    return moe_counters.touched_per_layer(result)
