"""Percentiles and the arithmetic from client records to end-to-end numbers.

A record is one request as its client saw it, all times in seconds on the
harness's ``time.perf_counter`` with the window opening at 0::

    {"due": 3.20, "sent": 3.2004, "stamps": [3.41, 3.47, ...],
     "prompt_len": 256, "asked": 120, "done": 10.9 or None, "error": None}

``due`` is when the schedule wanted it sent (for a closed loop, when its
client was free), ``stamps`` one per streamed token, ``done`` when the stream
ended having returned ``asked`` tokens. Latency is counted from ``due``: a
stall that delays later requests counts against them (choosing-metrics,
section 5). The repo's ``loadgen.HandleTarget`` times from ``sent``.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in [0, 100]); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Iterable[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's measure."""
    xs = list(values)
    mid = median(xs)
    if not mid:
        return None
    return (percentile(xs, 75.0) - percentile(xs, 25.0)) / abs(mid)


def ttft_s(record: dict) -> Optional[float]:
    """First streamed token minus the time the request was due."""
    if not record["stamps"]:
        return None
    return record["stamps"][0] - record["due"]


def tpot_s(record: dict) -> Optional[float]:
    """(last token - first token) / (tokens - 1) of a completed request: a
    per-request mean, so a prefill that interrupts decode counts."""
    stamps = record["stamps"]
    if record["done"] is None or len(stamps) < 2:
        return None
    return (stamps[-1] - stamps[0]) / (len(stamps) - 1)


def due_in(records: List[dict], start: float, end: float) -> List[dict]:
    return [r for r in records if start <= r["due"] < end]


def ttfts_s(records: List[dict], start: float, end: float) -> List[float]:
    """Times to first token of the requests due in [start, end) that got one."""
    return [t for t in map(ttft_s, due_in(records, start, end)) if t is not None]


def completed_in(records: List[dict], start: float, end: float) -> List[dict]:
    return [
        r for r in records
        if r["done"] is not None and start <= r["done"] < end
    ]


def tokens_in(records: List[dict], start: float, end: float) -> int:
    """Output tokens received with a timestamp inside [start, end)."""
    return sum(1 for r in records for t in r["stamps"] if start <= t < end)


def inter_token_gaps(records: List[dict], start: float, end: float) -> List[float]:
    """Gaps between consecutive tokens of one stream, pooled over streams,
    for gaps that end inside [start, end)."""
    gaps = []
    for r in records:
        s = r["stamps"]
        gaps.extend(b - a for a, b in zip(s, s[1:]) if start <= b < end)
    return gaps


def lateness(records: List[dict], start: float, end: float) -> List[float]:
    """Sent minus due: how late the generator ran."""
    return [r["sent"] - r["due"] for r in due_in(records, start, end)]


def failed(record: dict) -> bool:
    """A request that raised, was refused, or ended with another number of
    tokens than asked. One cut off by the end of the run has not failed, but
    may not have streamed more than it asked for."""
    if record["error"] is not None:
        return True
    if record["done"] is not None:
        return len(record["stamps"]) != record["asked"]
    return len(record["stamps"]) > record["asked"]
