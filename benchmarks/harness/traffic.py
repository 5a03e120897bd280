"""The one general traffic generator: a mix file's parameters and a seed in,
a list of requests out. New traffic is a new data file, never new code here.

Mix file keys read here (``benchmarks/traffic/<mix>.json``):

  prompt_lens      {"128": 0.5, "256": 0.3, ...}  length -> weight
  output_tokens    [lo, hi]  uniform over the range, inclusive
  arrivals         {"process": "poisson", "rate_per_s": 1.2, "count": "fixed"}
                   or {"process": "gamma", "shape": 0.5, ...}: gaps are gamma
                   distributed (shape 1 is Poisson; smaller is burstier,
                   larger steadier). "count": "fixed" sends exactly
                   round(rate x span) requests, their gaps scaled to fill the
                   span, in the ramp before the window (negative times) and
                   in the window each: for Poisson that is the process
                   conditioned on its counts, so every seed offers the
                   window the same amount of work and only the moments
                   differ; "random" (the default) lets the count vary too.
                   Absent for a closed loop, whose clients send as they
                   become free
  shared_prefix    absent: every prompt is independent random ids

Lengths are *stratified*: each consecutive group of 20 requests holds every
prompt length in proportion to its weight and 20 output lengths evenly
spaced over their range, each in an order shuffled by the seed. A run then
carries the same amount of work whatever the seed, and only its order and
its arrival times vary: with independent draws the offered tokens of a
35-request window varied by 6% between seeds, and the medians with them.
Copied in spirit from ``ray_tpu/loadgen`` (``synthesize``,
``PoissonArrivals``), which draws each length independently.
"""

from __future__ import annotations

import random
from typing import Iterator, List


GROUP = 20


def _cycle(block: list, rng: random.Random) -> Iterator[int]:
    """Endless values: ``block`` again and again, shuffled anew each time."""
    block = list(block)
    while True:
        rng.shuffle(block)
        yield from block


def _prompt_block(weights: dict) -> list:
    lengths = sorted(int(k) for k in weights)
    total = sum(weights.values())
    block = [n for n in lengths
             for _ in range(round(GROUP * weights[str(n)] / total))]
    if not block:
        raise ValueError(f"prompt_lens weights too small for a group of {GROUP}")
    return block


def _output_block(lo: int, hi: int) -> list:
    return [round(lo + (hi - lo) * k / (GROUP - 1)) for k in range(GROUP)]


def requests(mix: dict, vocab_size: int, seed: int, stream: int = 0) -> Iterator[dict]:
    """Endless seeded requests of this mix. ``stream`` separates the clients
    of a closed loop, which each draw their own sequence."""
    rng = random.Random(seed * 1_000_003 + stream * 7919 + 17)
    prompts = _cycle(_prompt_block(mix["prompt_lens"]), rng)
    outputs = _cycle(_output_block(*mix["output_tokens"]), rng)
    for n, asked in zip(prompts, outputs):
        yield {
            "token_ids": [rng.randrange(3, vocab_size - 1) for _ in range(n)],
            "max_new_tokens": asked,
        }


def arrival_times(arrivals: dict, start_s: float, end_s: float, seed: int) -> List[float]:
    """Seeded arrival offsets in [start_s, end_s)."""
    rate = float(arrivals["rate_per_s"])
    shape = {"poisson": 1.0, "gamma": float(arrivals.get("shape", 1.0))}[
        arrivals["process"]
    ]
    rng = random.Random(seed * 1_000_003 + 29)
    if arrivals.get("count", "random") == "fixed":
        spans = [(start_s, end_s)]
        if start_s < 0.0 < end_s:  # the ramp and the window: a count each
            spans = [(start_s, 0.0), (0.0, end_s)]
        out = []
        for a, b in spans:
            n = round(rate * (b - a))
            gaps = [rng.gammavariate(shape, 1.0) for _ in range(n + 1)]
            scale, t = (b - a) / sum(gaps), a
            for gap in gaps[:-1]:
                t += gap * scale
                out.append(t)
        return out
    t, out = start_s, []
    while True:
        t += rng.gammavariate(shape, 1.0 / (rate * shape))
        if t >= end_s:
            return out
        out.append(t)
